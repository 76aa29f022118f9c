"""Counting rules for the operations and bytes of a training step, and the
counts that several architectures share.

Each architecture module (``bench/arch/<model>.py``) counts its own step
from its configuration file by these rules: the work the algorithm needs,
whatever implements it: no recomputation, no padding (neither padded rows
nor the kernel's padding of head_dim to the 128-lane width), only the
(query, key) pairs the mask keeps, and each tensor of a kernel read or
written once.
"""

from __future__ import annotations

from typing import Optional

F32 = 4  # bytes: the configurations train in float32


def attention_pairs(seq: int, window: Optional[int] = None) -> int:
    """(query, key) pairs a causal mask keeps in one head of one row: key
    k for query q where 0 <= q - k, and q - k < ``window`` where given
    (the whole causal half where it is None)."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def flash_work_per_row(seq: int, heads: int, kv_heads: int, head_dim: int,
                       window: Optional[int] = None) -> tuple[float, float]:
    """(FLOPs, bytes) the flash-attention kernels need for one valid row of
    one layer, forward and backward together.

    FLOPs: the forward's QK^T and PV (4·dh per kept pair and head) and
    the backward's dV, dP, dQ and dK products (8·dh); the backward's
    recomputation of the scores is not counted.
    Bytes, float32: the forward reads q, k, v and writes o and the
    per-row logsumexp; the backward reads q, k, v, o, dO and the
    logsumexp and writes dq, dk and dv.
    """
    flops = 12 * head_dim * heads * attention_pairs(seq, window)
    q = seq * heads * head_dim * F32
    kv = seq * kv_heads * head_dim * F32
    lse = seq * heads * F32
    fwd = q + 2 * kv + q + lse
    bwd = (q + 2 * kv + q + q + lse) + (q + 2 * kv)
    return float(flops), float(fwd + bwd)


def flash_work(seq: int, heads: int, kv_heads: int, head_dim: int,
               rows: int, window: Optional[int] = None) -> tuple[float, float]:
    """(FLOPs, bytes) of the flash kernels over ``rows`` (valid rows times
    the layers that run them)."""
    f, b = flash_work_per_row(seq, heads, kv_heads, head_dim, window)
    return f * rows, b * rows
