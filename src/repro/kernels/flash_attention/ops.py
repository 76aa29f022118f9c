"""Jitted, differentiable public wrapper for the flash-attention kernel.

pallas_call has no autodiff rule, so `attention` installs a custom_vjp:
forward = the Pallas kernel (saving the (out, lse) flash residuals);
backward = the dedicated Pallas backward kernels (DESIGN.md §14).  The
pure-jnp recompute through `attention_ref` survives as ``bwd_impl="oracle"``
— the interpret-mode correctness reference the Pallas backward is tested
against (tests/test_kernel_ragged.py), never the default path.

Raggedness: ``num_valid`` rides along as a *traced* int32 operand (its
cotangent is None), so the bucket ladder's per-shape executables serve
every valid count without recompiling — the same mask the trainer applies
to the loss is the kernel's row-skip count (train/mesh.py fetch contract).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import (flash_attention,
                                                 flash_attention_bwd)
from repro.kernels.flash_attention.ref import attention_ref


def _mask_rows(x, nv):
    """Zero rows >= nv along the batch axis (the kernel's padded-row
    semantics, applied to the reference path for exact comparability)."""
    rows = jax.lax.broadcasted_iota(
        jnp.int32, (x.shape[0],) + (1,) * (x.ndim - 1), 0)
    return jnp.where(rows < nv, x, 0.0).astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10,
                                                    11, 12))
def _attention(q, k, v, nv, ragged, causal, window, softcap, block_q,
               block_k, interpret, bwd_impl, ragged_impl):
    return flash_attention(
        q, k, v, num_valid=nv if ragged else None, ragged_impl=ragged_impl,
        causal=causal, window=window, softcap=softcap, block_q=block_q,
        block_k=block_k, interpret=interpret)


def _fwd(q, k, v, nv, ragged, causal, window, softcap, block_q, block_k,
         interpret, bwd_impl, ragged_impl):
    out, lse = flash_attention(
        q, k, v, num_valid=nv if ragged else None, ragged_impl=ragged_impl,
        causal=causal, window=window, softcap=softcap, block_q=block_q,
        block_k=block_k, interpret=interpret, return_lse=True)
    return out, (q, k, v, out, lse, nv)


def _bwd(ragged, causal, window, softcap, block_q, block_k, interpret,
         bwd_impl, ragged_impl, res, g):
    q, k, v, out, lse, nv = res
    if bwd_impl == "oracle":
        # recompute-based gradients through the jnp oracle, with the
        # kernel's ragged semantics (zeroed padded rows) replicated so the
        # two backends are drop-in comparable
        def f(q_, k_, v_):
            o = attention_ref(q_, k_, v_, causal=causal, window=window,
                              softcap=softcap)
            return _mask_rows(o, nv) if ragged else o

        _, vjp = jax.vjp(f, q, k, v)
        dq, dk, dv = vjp(g)
    else:
        dq, dk, dv = flash_attention_bwd(
            q, k, v, g, out, lse, num_valid=nv if ragged else None,
            ragged_impl=ragged_impl, causal=causal, window=window,
            softcap=softcap, block_q=block_q, block_k=block_k,
            interpret=interpret)
    return dq, dk, dv, None  # num_valid: integer operand, no cotangent


_attention.defvjp(_fwd, _bwd)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "block_q", "block_k",
                     "interpret", "use_kernel", "bwd_impl", "ragged_impl"))
def attention(q, k, v, *, num_valid=None, causal: bool = True,
              window: Optional[int] = None,
              softcap: Optional[float] = None,
              block_q: Optional[int] = None, block_k: Optional[int] = None,
              interpret: bool = False,
              use_kernel: bool = True, bwd_impl: str = "pallas",
              ragged_impl: str = "auto"):
    """Differentiable attention on the kernel (or reference) backend.

    num_valid: optional traced int32 — with a bucket-padded batch, rows
    >= num_valid cost no kernel FLOPs and get exact-zero outputs/grads;
    requires the trainer's suffix-padding contract (valid rows form a
    prefix — train/mesh.py).  bwd_impl: "pallas" (default) or "oracle"
    (jnp recompute reference).  ragged_impl: see kernels/.../kernel.py.
    block_q/block_k: None lets ``kernel.tile_plan`` choose from the shape.
    """
    if bwd_impl not in ("pallas", "oracle"):
        raise ValueError(f"unknown bwd_impl {bwd_impl!r}")
    ragged = num_valid is not None
    if not use_kernel:
        out = attention_ref(q, k, v, causal=causal, window=window,
                            softcap=softcap)
        return _mask_rows(out, num_valid) if ragged else out
    nv = (jnp.asarray(num_valid, jnp.int32).reshape(())
          if ragged else jnp.int32(q.shape[0]))
    return _attention(q, k, v, nv, ragged, causal, window, softcap,
                      block_q, block_k, interpret, bwd_impl, ragged_impl)
