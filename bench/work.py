"""Operations and bytes of a training step, computed from the shapes of a
configuration file (``bench/configs/<name>.json``, Hugging Face key names).

These are the work the algorithm needs, whatever implements it: no
recomputation, no padding (neither padded rows nor the kernel's padding of
head_dim to the 128-lane width), the causal half of attention, and each
tensor of the attention kernels read or written once.
"""

from __future__ import annotations

F32 = 4  # bytes: the configurations train in float32


def _dims(conf: dict):
    d = conf["hidden_size"]
    h = conf["num_attention_heads"]
    hkv = conf["num_key_value_heads"]
    dh = conf.get("head_dim") or d // h
    return d, h, hkv, dh, conf["intermediate_size"], conf["vocab_size"]


def layer_matmul_params(conf: dict) -> int:
    """Weights of one decoder layer that multiply activations: the q, k, v
    and output projections and the SwiGLU gate, up and down matrices."""
    d, h, hkv, dh, ff, _ = _dims(conf)
    return d * h * dh + 2 * d * hkv * dh + h * dh * d + 3 * d * ff


def matmul_params(conf: dict) -> int:
    """Weights that multiply activations in one forward pass: every layer
    and the output head (the embedding is a lookup, not a matmul)."""
    d, *_, v = _dims(conf)
    return conf["num_hidden_layers"] * layer_matmul_params(conf) + d * v


def param_count(conf: dict) -> int:
    """Every parameter: matmul weights, the embedding table and the RMSNorm
    scales (two per layer and the final one)."""
    d, *_, v = _dims(conf)
    return (matmul_params(conf) + v * d
            + (2 * conf["num_hidden_layers"] + 1) * d)


def causal_pairs(seq: int) -> int:
    """(query, key) pairs a causal mask keeps in one head of one row."""
    return seq * (seq + 1) // 2


def train_flops_per_position(conf: dict, seq: int) -> float:
    """Useful FLOPs of a forward and backward pass, per position of a
    ``seq``-long row: 6 per matmul weight (2 forward, 4 backward) plus the
    two attention products (QK^T and PV) over the causal pairs, three
    times over for the backward."""
    d, h, hkv, dh, *_ = _dims(conf)
    attn = conf["num_hidden_layers"] * 3 * 4 * dh * h * causal_pairs(seq)
    return 6 * matmul_params(conf) + attn / seq


def flash_work_per_row(conf: dict, seq: int) -> tuple[float, float]:
    """(FLOPs, bytes) the flash-attention kernels need for one valid row of
    one layer, forward and backward together.

    FLOPs: the forward's QK^T and PV (4·dh per causal pair and head) and
    the backward's dV, dP, dQ and dK products (8·dh); the backward's
    recomputation of the scores is not counted.
    Bytes, float32: the forward reads q, k, v and writes o and the
    per-row logsumexp; the backward reads q, k, v, o, dO and the
    logsumexp and writes dq, dk and dv.
    """
    d, h, hkv, dh, *_ = _dims(conf)
    flops = 12 * dh * h * causal_pairs(seq)
    q = seq * h * dh * F32
    kv = seq * hkv * dh * F32
    lse = seq * h * F32
    fwd = q + 2 * kv + q + lse
    bwd = (q + 2 * kv + q + q + lse) + (q + 2 * kv)
    return float(flops), float(fwd + bwd)


def flash_work(conf: dict, seq: int, valid_rows: int) -> tuple[float, float]:
    """(FLOPs, bytes) of the flash kernels over ``valid_rows`` rows, all
    layers."""
    f, b = flash_work_per_row(conf, seq)
    n = valid_rows * conf["num_hidden_layers"]
    return f * n, b * n
