"""Flash attention as a Pallas TPU kernel — ragged forward + backward.

TPU-native design (DESIGN.md §14):
  * grid (batch, q_heads, num_q_blocks, num_kv_blocks) — the last axis is
    sequential on TPU, so the online-softmax running state (m, l, acc) lives
    in VMEM scratch that persists across kv-block iterations;
  * head_dim is zero-padded to the 128-lane register width inside this
    module (whisper's 64 and the reduced configs' 32: zero K/V lanes add
    zero to every dot product and the padded output/grad lanes are sliced
    off);
  * lane-dense blocks: the public (B, S, H, D) tensors are viewed, for
    free, as (B, S, H·D), and each program moves one (block, D) tile whose
    lane block is its head — both trailing block dims are multiples of
    (8, 128), the Mosaic tiling rule, with no head-major transpose in HBM;
  * per-row statistics (m, l, lse, delta) are lane-replicated
    (rows, 128) tiles, the layout of the reference TPU kernel in
    ``jax.experimental.pallas.ops.tpu.flash_attention``; the public
    ``lse`` residual stays (B, H, S) and is broadcast to lanes only for the
    backward call;
  * GQA is expressed in the K/V index_map (query head h reads kv head
    h // rep) — no materialized head repetition in HBM;
  * the tile is chosen from the shape (``tile_plan``): the widest of 512,
    256 and 128 that divides the sequence, no wider than a sliding window
    rounded up to 128.  A grid step costs a fixed ~0.35 us on a TPU v5e
    whatever its tile holds, so at seq 4096 512-wide tiles launch 64 steps
    per (row, head) where 128-wide ones launch 1024;
  * causal + sliding-window masking is applied per tile; fully-masked tiles
    short-circuit via @pl.when so the MXU never sees them, and their index
    maps are clamped into the visible band (``_kv_band``/``_q_band``): a
    masked step names the block its neighbouring visible step holds, and
    the pipeline copies nothing for a block index that repeats (the
    reference TPU kernel's ``below_or_on_diag`` trick), so a masked step
    moves no data;
  * float32 inputs get float32 MXU passes (``precision=HIGHEST``): Mosaic's
    default runs a float32 dot as one bfloat16 pass.

Ragged batches (the bucket-ladder hot path, DESIGN.md §14): ``num_valid``
is a *traced* int32 — one compiled executable per bucket shape serves every
valid count.  It is threaded three ways, belt and braces:
  * the batch grid extent itself is ``num_valid`` (Pallas grids accept
    dynamic dimensions), so programs for padded rows are never launched;
  * ``num_valid`` is also scalar-prefetched into the kernel and every
    program guards on ``batch_index < num_valid`` via @pl.when, so a
    static-grid fallback still skips padded-row compute at tile granularity;
  * index maps clamp the batch coordinate below ``num_valid`` so a guarded
    program can never prefetch an out-of-range block.
Padded rows of every output (and every gradient) are written as exact
zeros — never NaN/garbage — because downstream masked reductions multiply
them by zero and ``0 * NaN`` would poison the whole gradient.

``ragged_impl`` selects how raggedness executes:
  * ``"grid"``  — dynamic batch-grid extent as above (the TPU form);
  * ``"rowloop"`` — the batch axis hoisted into a ``lax.fori_loop`` with
    trip count ``num_valid``, each row a b=1 pallas_call.  Semantically
    identical (a TPU batch grid axis IS a sequential outer loop); this form
    also realizes the wall-clock skip under interpret mode, where the
    in-grid emulation pays per-program overhead proportional to the full
    buffer (measured in benchmarks/kernel_bench.py);
  * ``"auto"`` — rowloop under interpret, grid otherwise.

Validated on CPU with interpret=True against ref.attention_ref (forward)
and the jnp-oracle vjp (backward, tests/test_kernel_ragged.py); compiled
for a described v5e chip in tests/test_tpu_compile.py.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANE = 128  # TPU register lane width: last block dim should be a multiple
_TRANS_B = (((1,), (1,)), ((), ()))  # a @ b.T without materializing b.T


def _precision(dtype):
    """MXU precision for kernel inputs of ``dtype``: full float32 passes
    for float32 (the default would round operands to bfloat16); narrower
    inputs lose nothing at the default."""
    return jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None


def _ceil_to(x: int, m: int) -> int:
    return -(-x // m) * m


def _pad_lanes(x):
    """Zero-pad head_dim up to the 128-lane width (identity if aligned)."""
    d = x.shape[-1]
    dp = _ceil_to(d, LANE)
    if dp == d:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, dp - d)])


def _fold(x):
    """(B, S, H, D) -> (B, S, H·D): head h becomes lane block h (free)."""
    b, s, h, d = x.shape
    return x.reshape(b, s, h * d)


def _lanes(x, n: int):
    """Lane-replicated (rows, LANE) statistic -> (rows, n)."""
    if n <= LANE:
        return x[:, :n]
    if n % LANE:
        raise ValueError(f"block width {n} must be <= {LANE} or a multiple")
    return jnp.tile(x, (1, n // LANE))


def _row_stat(x):
    """(B, H, S) per-row statistic -> lane-replicated (B, H, S, LANE)."""
    return jnp.broadcast_to(x[..., None], x.shape + (LANE,))


def _resolve_impl(ragged_impl: str, interpret: bool) -> str:
    if ragged_impl == "auto":
        return "rowloop" if interpret else "grid"
    if ragged_impl not in ("grid", "rowloop"):
        raise ValueError(f"unknown ragged_impl {ragged_impl!r}")
    return ragged_impl


def _guarded(gate, fn):
    """Run fn under @pl.when(gate); a Python-True gate runs unconditionally."""
    if gate is True:
        fn()
    else:
        pl.when(gate)(fn)


class TilePlan(NamedTuple):
    """The tile schedule of one (row, head) of the causal grid."""
    block_q: int
    block_k: int
    grid_steps: int     # programs each kernel launches: (seq_q/bq)(seq_k/bk)
    visible_tiles: int  # of those, the ones holding a visible (q, k) pair


TILES = (512, 256, 128)  # widest first; (1024, 512) overflows the bwd's VMEM


def _auto_block(n: int, cap: Optional[int]) -> int:
    """The widest of TILES dividing ``n`` and at most ``cap``; sequences
    no tile divides keep the old default, min(128, n)."""
    for tile in TILES:
        if n % tile == 0 and (cap is None or tile <= cap):
            return tile
    return min(LANE, n)


def tile_plan(seq_q: int, seq_k: int, *, causal: bool = True,
              window: Optional[int] = None, block_q: Optional[int] = None,
              block_k: Optional[int] = None) -> TilePlan:
    """Blocks for a (seq_q, seq_k) attention and what they cost per head.

    A block left None is chosen from the shape: the widest tile that
    divides the sequence, no wider than the window rounded up to the lane
    width (a wider tile would hold mostly masked pairs).  An explicit
    block is kept, capped at its sequence."""
    cap = None if window is None else _ceil_to(window, LANE)
    bq = _auto_block(seq_q, cap) if block_q is None else min(block_q, seq_q)
    bk = _auto_block(seq_k, cap) if block_k is None else min(block_k, seq_k)
    if seq_q % bq or seq_k % bk:
        raise ValueError(
            f"seq ({seq_q},{seq_k}) must divide blocks ({bq},{bk})")
    nq, nk = seq_q // bq, seq_k // bk
    visible = sum(bool(_tile_visible(
        iq, ik, block_q=bq, block_k=bk, seq_q=seq_q, seq_k=seq_k,
        causal=causal, window=window)) for iq in range(nq) for ik in range(nk))
    return TilePlan(bq, bk, nq * nk, visible)


def _kv_band(iq, nk, *, block_q, block_k, seq_q, seq_k, causal, window):
    """[first, last] kv blocks ``_tile_visible`` passes for q block ``iq``
    (traced), clipped to the grid; empty when first > last; the whole
    grid when nothing masks."""
    q_first = iq * block_q + (seq_k - seq_q)
    lo, hi = 0, nk - 1
    if causal:
        hi = jnp.clip((q_first + block_q - 1) // block_k, -1, nk - 1)
    if window is not None:
        lo = jnp.clip((q_first - window + 1) // block_k, 0, nk)
    return lo, hi


def _q_band(ik, nq, *, block_q, block_k, seq_q, seq_k, causal, window):
    """[first, last] q blocks ``_tile_visible`` passes for kv block ``ik``
    (traced), clipped to the grid; empty when first > last."""
    k_first = ik * block_k - (seq_k - seq_q)   # in query coordinates
    lo, hi = 0, nq - 1
    if causal:
        lo = jnp.clip(k_first // block_q, 0, nq)
    if window is not None:
        hi = jnp.clip((k_first + block_k + window - 2) // block_q, -1, nq - 1)
    return lo, hi


def _in_band(i, band):
    """Block index ``i`` moved into its band, so that a step the kernel
    skips names the block a neighbouring visible step already holds and
    the pipeline issues no copy for it.  Visible steps are unchanged; an
    empty band still yields an index inside the grid."""
    lo, hi = band
    return jnp.minimum(jnp.maximum(i, lo), jnp.maximum(hi, 0))


def _tile_visible(iq, ik, *, block_q, block_k, seq_q, seq_k, causal, window):
    """Scalar predicate: does tile (iq, ik) contain any visible (q, k) pair?
    (queries right-aligned when seq_q < seq_k: decode; Python ints give a
    Python bool)"""
    q_first = iq * block_q + (seq_k - seq_q)
    q_last = q_first + block_q - 1
    k_first = ik * block_k
    k_last = ik * block_k + block_k - 1
    visible = True
    if causal:
        visible = k_first <= q_last
    if window is not None:
        vis_w = k_last > q_first - window
        visible = visible & vis_w
    return visible


def _tile_mask(iq, ik, *, block_q, block_k, seq_q, seq_k, causal, window):
    """(block_q, block_k) bool visibility mask, or None if nothing masks."""
    if not causal and window is None:
        return None
    q_pos = iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0) + (seq_k - seq_q)
    k_pos = ik * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = jnp.ones((block_q, block_k), jnp.bool_)
    if causal:
        mask = jnp.logical_and(mask, k_pos <= q_pos)
    if window is not None:
        mask = jnp.logical_and(mask, k_pos > q_pos - window)
    return mask


def _gate(valid, visible):
    """Combine the ragged row guard with the tile-visibility predicate."""
    if valid is True:
        return visible
    return valid if visible is True else jnp.logical_and(valid, visible)


def _bsel(b_, nvr):
    """Clamp a padded row's batch coordinate to 0 (no out-of-range DMA)."""
    return b_ if nvr is None else jnp.where(b_ < nvr[0], b_, 0)


def _pallas(kernel, nv, *, grid, in_specs, out_specs, out_shape, scratch,
            interpret, args):
    """One pallas_call; ragged calls scalar-prefetch ``nv`` and pass it to
    every index map as its last argument.  Index maps are written as
    ``fn(b, h, i2, i3, nvr=None)``."""
    if nv is None:
        return pl.pallas_call(
            kernel, grid=grid,
            in_specs=[pl.BlockSpec(blk, fn) for blk, fn in in_specs],
            out_specs=[pl.BlockSpec(blk, fn) for blk, fn in out_specs],
            out_shape=out_shape, scratch_shapes=scratch,
            interpret=interpret)(*args)

    def spec(blk, fn):
        return pl.BlockSpec(blk, lambda *ix: fn(*ix[:-1], nvr=ix[-1]))

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid,
            in_specs=[spec(blk, fn) for blk, fn in in_specs],
            out_specs=[spec(blk, fn) for blk, fn in out_specs],
            scratch_shapes=scratch),
        out_shape=out_shape, interpret=interpret)(nv, *args)


def _zero_padded_rows(nv, *xs):
    """Rows the dynamic grid never launched hold uninitialized memory."""
    if nv is None:
        return xs
    return tuple(jnp.where(
        jax.lax.broadcasted_iota(jnp.int32, (x.shape[0],) + (1,) * (x.ndim - 1),
                                 0) < nv[0], x, 0.0).astype(x.dtype)
        for x in xs)


def _grid_rows(nv, b):
    """(batch-grid extent, prefetch operand): the extent is dynamic when
    ragged, so programs for padded rows are never launched."""
    if nv is None:
        return b, None
    nv = jnp.asarray(nv, jnp.int32).reshape(-1)[:1]
    return jnp.clip(nv[0], 0, b), nv


# ----------------------------------------------------------------- forward


def _fwd_kernel(*refs, block_q, block_k, seq_q, seq_k, causal, window,
                softcap, sm_scale, ragged):
    if ragged:
        nv_ref, *refs = refs
    q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref = refs
    bi = pl.program_id(0)
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)
    valid = (bi < nv_ref[0]) if ragged else True
    d = acc_ref.shape[-1]

    @pl.when(ik == 0)
    def init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    geom = dict(block_q=block_q, block_k=block_k, seq_q=seq_q, seq_k=seq_k,
                causal=causal, window=window)

    prec = _precision(q_ref.dtype)

    def compute():
        q = q_ref[...].astype(jnp.float32)                  # (bq, d)
        k = k_ref[...].astype(jnp.float32)                  # (bk, d)
        v = v_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, _TRANS_B, precision=prec,
                                preferred_element_type=jnp.float32) * sm_scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        mask = _tile_mask(iq, ik, **geom)
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                                 # (bq, LANE)
        m_cur = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - _lanes(m_cur, block_k))
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * _lanes(alpha, d) + jnp.dot(
            p, v, precision=prec, preferred_element_type=jnp.float32)
        m_ref[...] = m_cur

    _guarded(_gate(valid, _tile_visible(iq, ik, **geom)), compute)

    @pl.when(ik == nk - 1)
    def finalize():
        l_safe = jnp.maximum(l_ref[...], 1e-20)
        out = acc_ref[...] / _lanes(l_safe, d)
        lse = m_ref[...] + jnp.log(l_safe)
        if ragged:  # padded rows must be finite zeros, never garbage
            out = jnp.where(valid, out, 0.0)
            lse = jnp.where(valid, lse, 0.0)
        o_ref[...] = out.astype(o_ref.dtype)
        lse_ref[...] = lse


def _fwd_call(q, k, v, nv, *, causal, window, softcap, sm_scale,
              block_q, block_k, interpret):
    """One pallas_call on lane-padded tensors -> (out, lse (B,H,S) f32)."""
    b, s, h, d = q.shape
    t, hkv = k.shape[1], k.shape[2]
    rep = h // hkv
    nb, nv = _grid_rows(nv, b)
    geom = dict(block_q=block_q, block_k=block_k, seq_q=s, seq_k=t,
                causal=causal, window=window)
    kernel = functools.partial(
        _fwd_kernel, **geom, softcap=softcap, sm_scale=sm_scale,
        ragged=nv is not None)

    def q_at(b_, h_, iq, ik, nvr=None):
        return (_bsel(b_, nvr), iq, h_)

    def kv_at(b_, h_, iq, ik, nvr=None):
        ik = _in_band(ik, _kv_band(iq, t // block_k, **geom))
        return (_bsel(b_, nvr), ik, h_ // rep)

    out, lse = _pallas(
        kernel, nv, grid=(nb, h, s // block_q, t // block_k),
        in_specs=[((None, block_q, d), q_at),
                  ((None, block_k, d), kv_at),
                  ((None, block_k, d), kv_at)],
        out_specs=[((None, block_q, d),
                    lambda b_, h_, iq, ik, nvr=None: (b_, iq, h_)),
                   ((None, None, block_q, LANE),
                    lambda b_, h_, iq, ik, nvr=None: (b_, h_, iq, 0))],
        out_shape=[jax.ShapeDtypeStruct((b, s, h * d), q.dtype),
                   jax.ShapeDtypeStruct((b, h, s, LANE), jnp.float32)],
        scratch=[pltpu.VMEM((block_q, LANE), jnp.float32),
                 pltpu.VMEM((block_q, LANE), jnp.float32),
                 pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret, args=(_fold(q), _fold(k), _fold(v)))
    out, lse = _zero_padded_rows(nv, out.reshape(b, s, h, d), lse[..., 0])
    return out, lse


def _fwd_rowloop(q, k, v, nv, **kw):
    """Batch axis hoisted to a dynamic-trip fori_loop of b=1 calls."""
    b, s, h, _ = q.shape
    out0 = jnp.zeros(q.shape, q.dtype)
    lse0 = jnp.zeros((b, h, s), jnp.float32)

    def body(i, carry):
        out, lse = carry
        sl = lambda x: jax.lax.dynamic_slice_in_dim(x, i, 1, 0)
        o1, l1 = _fwd_call(sl(q), sl(k), sl(v), None, **kw)
        out = jax.lax.dynamic_update_slice_in_dim(out, o1, i, 0)
        lse = jax.lax.dynamic_update_slice_in_dim(lse, l1, i, 0)
        return out, lse

    trip = jnp.clip(jnp.asarray(nv, jnp.int32).reshape(-1)[0], 0, b)
    return jax.lax.fori_loop(0, trip, body, (out0, lse0))


def flash_attention(q, k, v, *, num_valid=None, ragged_impl: str = "auto",
                    causal: bool = True, window: Optional[int] = None,
                    softcap: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: bool = False, return_lse: bool = False):
    """q: (B,S,H,D), k/v: (B,T,Hkv,D) with H % Hkv == 0 -> (B,S,H,D).

    num_valid: optional traced int32 — rows >= num_valid are skipped by the
    grid (not just masked) and their outputs are exact zeros; one compile
    per bucket shape covers every valid count.  return_lse additionally
    returns the per-row logsumexp (B,H,S) f32 residual for the backward
    kernels (zeros on padded rows).  block_q/block_k: None chooses the
    tile from the shape (``tile_plan``).
    """
    b, s, h, d = q.shape
    t, hkv = k.shape[1], k.shape[2]
    if h % hkv:
        raise ValueError(f"H={h} not divisible by Hkv={hkv}")
    plan = tile_plan(s, t, causal=causal, window=window, block_q=block_q,
                     block_k=block_k)
    kw = dict(causal=causal, window=window, softcap=softcap,
              sm_scale=1.0 / math.sqrt(d), block_q=plan.block_q,
              block_k=plan.block_k, interpret=interpret)
    qp, kp, vp = _pad_lanes(q), _pad_lanes(k), _pad_lanes(v)

    if num_valid is None:
        out, lse = _fwd_call(qp, kp, vp, None, **kw)
    elif _resolve_impl(ragged_impl, interpret) == "rowloop":
        out, lse = _fwd_rowloop(qp, kp, vp, num_valid, **kw)
    else:
        out, lse = _fwd_call(qp, kp, vp, num_valid, **kw)
    out = out[..., :d]
    return (out, lse) if return_lse else out


# ---------------------------------------------------------------- backward
#
# Standard flash backward split (DESIGN.md §14 memory plan): residuals are
# (q, k, v, out, lse) — O(B·S·H·D) like the inputs, never the (S, T) score
# matrix.  delta = rowsum(dO ⊙ O) is a cheap jnp reduction outside.  Two
# kernels because the two accumulators stream in opposite orders:
#   dq  : grid (B, H, nq, nk) — dq[iq] accumulates over k blocks;
#   dkv : grid (B, H, nk, nq) — dk/dv[ik] accumulate over q blocks
# each with VMEM scratch over the sequential last axis, the same trick as
# the forward's (m, l, acc).  Shared per-tile math:
#   p  = exp(s_soft - lse)  (masked)          ds = p * (dp - delta)
#   dp = dO V^T                               [softcap chain rule below]
#   dv += p^T dO      dq += ds K * sm_scale   dk += ds^T Q * sm_scale
# For GQA the kernels emit per-q-head dk/dv; the (Hkv, rep) group-sum
# happens outside (grad of the index-map head sharing).


def _bwd_tile(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, iq, ik, *,
              softcap, sm_scale, geom):
    """Shared per-tile backward math -> (q, k, do, p, ds); p, ds (bq, bk)."""
    bk = geom["block_k"]
    prec = _precision(q_ref.dtype)
    q = q_ref[...].astype(jnp.float32)
    k = k_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    do = do_ref[...].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, _TRANS_B, precision=prec,
                            preferred_element_type=jnp.float32) * sm_scale
    if softcap is not None:
        s_soft = softcap * jnp.tanh(s / softcap)
    else:
        s_soft = s
    p = jnp.exp(s_soft - _lanes(lse_ref[...], bk))
    mask = _tile_mask(iq, ik, **geom)
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    dp = jax.lax.dot_general(do, v, _TRANS_B, precision=prec,
                             preferred_element_type=jnp.float32)
    ds = p * (dp - _lanes(dl_ref[...], bk))
    if softcap is not None:  # d tanh: 1 - (s_soft / cap)^2
        ds = ds * (1.0 - jnp.square(s_soft / softcap))
    return q, k, do, p, ds


def _dq_kernel(*refs, block_q, block_k, seq_q, seq_k, causal, window,
               softcap, sm_scale, ragged):
    if ragged:
        nv_ref, *refs = refs
    q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, dq_ref, dq_acc = refs
    bi = pl.program_id(0)
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)
    valid = (bi < nv_ref[0]) if ragged else True

    @pl.when(ik == 0)
    def init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    geom = dict(block_q=block_q, block_k=block_k, seq_q=seq_q, seq_k=seq_k,
                causal=causal, window=window)

    def compute():
        _, k, _, _, ds = _bwd_tile(q_ref, k_ref, v_ref, do_ref, lse_ref,
                                   dl_ref, iq, ik, softcap=softcap,
                                   sm_scale=sm_scale, geom=geom)
        dq_acc[...] += jnp.dot(ds, k, precision=_precision(q_ref.dtype),
                               preferred_element_type=jnp.float32) * sm_scale

    _guarded(_gate(valid, _tile_visible(iq, ik, **geom)), compute)

    @pl.when(ik == nk - 1)
    def finalize():
        dq = dq_acc[...]
        if ragged:
            dq = jnp.where(valid, dq, 0.0)
        dq_ref[...] = dq.astype(dq_ref.dtype)


def _dkv_kernel(*refs, block_q, block_k, seq_q, seq_k, causal, window,
                softcap, sm_scale, ragged):
    if ragged:
        nv_ref, *refs = refs
    q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, dk_ref, dv_ref, \
        dk_acc, dv_acc = refs
    bi = pl.program_id(0)
    ik = pl.program_id(2)   # kv block: this program's output tile
    iq = pl.program_id(3)   # q block: the sequential accumulation axis
    nq = pl.num_programs(3)
    valid = (bi < nv_ref[0]) if ragged else True

    @pl.when(iq == 0)
    def init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    geom = dict(block_q=block_q, block_k=block_k, seq_q=seq_q, seq_k=seq_k,
                causal=causal, window=window)

    def compute():
        q, _, do, p, ds = _bwd_tile(q_ref, k_ref, v_ref, do_ref, lse_ref,
                                    dl_ref, iq, ik, softcap=softcap,
                                    sm_scale=sm_scale, geom=geom)
        prec = _precision(q_ref.dtype)
        dv_acc[...] += jnp.dot(p.T, do, precision=prec,
                               preferred_element_type=jnp.float32)
        dk_acc[...] += jnp.dot(ds.T, q, precision=prec,
                               preferred_element_type=jnp.float32) * sm_scale

    _guarded(_gate(valid, _tile_visible(iq, ik, **geom)), compute)

    @pl.when(iq == nq - 1)
    def finalize():
        dk, dv = dk_acc[...], dv_acc[...]
        if ragged:
            dk = jnp.where(valid, dk, 0.0)
            dv = jnp.where(valid, dv, 0.0)
        dk_ref[...] = dk.astype(dk_ref.dtype)
        dv_ref[...] = dv.astype(dv_ref.dtype)


def _bwd_call(q, k, v, do, lse, delta, nv, *, causal, window, softcap,
              sm_scale, block_q, block_k, interpret):
    """dq + dkv pallas_calls on lane-padded tensors.

    Returns (dq (B,S,H,D), dk (B,T,H,D), dv (B,T,H,D)) — dk/dv per q-head,
    GQA group-sum is the caller's job."""
    b, s, h, d = q.shape
    t, hkv = k.shape[1], k.shape[2]
    rep = h // hkv
    nb, nv = _grid_rows(nv, b)
    geom = dict(block_q=block_q, block_k=block_k, seq_q=s, seq_k=t,
                causal=causal, window=window)
    kw = dict(geom, softcap=softcap, sm_scale=sm_scale, ragged=nv is not None)
    nq, nk = s // block_q, t // block_k
    args = (_fold(q), _fold(k), _fold(v), _fold(do), _row_stat(lse),
            _row_stat(delta))
    call = functools.partial(_pallas, nv=nv, interpret=interpret, args=args)

    # ---- dq: grid (B, H, nq, nk), accumulate over the trailing k axis ----
    def q_at_2(b_, h_, i2, i3, nvr=None):
        return (_bsel(b_, nvr), i2, h_)

    def kv_at_3(b_, h_, i2, i3, nvr=None):
        i3 = _in_band(i3, _kv_band(i2, nk, **geom))
        return (_bsel(b_, nvr), i3, h_ // rep)

    def row_at_2(b_, h_, i2, i3, nvr=None):
        return (_bsel(b_, nvr), h_, i2, 0)

    (dq,) = call(
        functools.partial(_dq_kernel, **kw), grid=(nb, h, nq, nk),
        in_specs=[((None, block_q, d), q_at_2),               # q
                  ((None, block_k, d), kv_at_3),              # k
                  ((None, block_k, d), kv_at_3),              # v
                  ((None, block_q, d), q_at_2),               # do
                  ((None, None, block_q, LANE), row_at_2),    # lse
                  ((None, None, block_q, LANE), row_at_2)],   # delta
        out_specs=[((None, block_q, d),
                    lambda b_, h_, i2, i3, nvr=None: (b_, i2, h_))],
        out_shape=[jax.ShapeDtypeStruct((b, s, h * d), q.dtype)],
        scratch=[pltpu.VMEM((block_q, d), jnp.float32)])

    # ---- dkv: grid (B, H, nk, nq), accumulate over the trailing q axis ----
    def q_at_3(b_, h_, i2, i3, nvr=None):
        i3 = _in_band(i3, _q_band(i2, nq, **geom))
        return (_bsel(b_, nvr), i3, h_)

    def kv_at_2(b_, h_, i2, i3, nvr=None):
        return (_bsel(b_, nvr), i2, h_ // rep)

    def row_at_3(b_, h_, i2, i3, nvr=None):
        i3 = _in_band(i3, _q_band(i2, nq, **geom))
        return (_bsel(b_, nvr), h_, i3, 0)

    def out_kv_at_2(b_, h_, i2, i3, nvr=None):
        return (b_, i2, h_)

    dk, dv = call(
        functools.partial(_dkv_kernel, **kw), grid=(nb, h, nk, nq),
        in_specs=[((None, block_q, d), q_at_3),               # q
                  ((None, block_k, d), kv_at_2),              # k
                  ((None, block_k, d), kv_at_2),              # v
                  ((None, block_q, d), q_at_3),               # do
                  ((None, None, block_q, LANE), row_at_3),    # lse
                  ((None, None, block_q, LANE), row_at_3)],   # delta
        out_specs=[((None, block_k, d), out_kv_at_2),
                   ((None, block_k, d), out_kv_at_2)],
        out_shape=[jax.ShapeDtypeStruct((b, t, h * d), k.dtype),
                   jax.ShapeDtypeStruct((b, t, h * d), v.dtype)],
        scratch=[pltpu.VMEM((block_k, d), jnp.float32),
                 pltpu.VMEM((block_k, d), jnp.float32)])

    return _zero_padded_rows(nv, dq.reshape(b, s, h, d),
                             dk.reshape(b, t, h, d), dv.reshape(b, t, h, d))


def _bwd_rowloop(q, k, v, do, lse, delta, nv, **kw):
    b = q.shape[0]
    t, h = k.shape[1], q.shape[2]
    d = q.shape[-1]
    zeros = (jnp.zeros(q.shape, q.dtype),
             jnp.zeros((b, t, h, d), k.dtype),
             jnp.zeros((b, t, h, d), v.dtype))

    def body(i, carry):
        dq, dk, dv = carry
        sl = lambda x: jax.lax.dynamic_slice_in_dim(x, i, 1, 0)
        dq1, dk1, dv1 = _bwd_call(sl(q), sl(k), sl(v), sl(do), sl(lse),
                                  sl(delta), None, **kw)
        dq = jax.lax.dynamic_update_slice_in_dim(dq, dq1, i, 0)
        dk = jax.lax.dynamic_update_slice_in_dim(dk, dk1, i, 0)
        dv = jax.lax.dynamic_update_slice_in_dim(dv, dv1, i, 0)
        return dq, dk, dv

    trip = jnp.clip(jnp.asarray(nv, jnp.int32).reshape(-1)[0], 0, b)
    return jax.lax.fori_loop(0, trip, body, zeros)


def flash_attention_bwd(q, k, v, do, out, lse, *, num_valid=None,
                        ragged_impl: str = "auto", causal: bool = True,
                        window: Optional[int] = None,
                        softcap: Optional[float] = None,
                        block_q: Optional[int] = None,
                        block_k: Optional[int] = None,
                        interpret: bool = False):
    """Pallas backward: (dq, dk, dv) for the flash_attention forward.

    do/out/lse are the upstream cotangent and the forward's saved
    (output, logsumexp) residuals.  Raggedness mirrors the forward: padded
    rows contribute nothing and receive exact-zero gradients."""
    b, s, h, d = q.shape
    t, hkv = k.shape[1], k.shape[2]
    rep = h // hkv
    plan = tile_plan(s, t, causal=causal, window=window, block_q=block_q,
                     block_k=block_k)
    # delta = rowsum(dO . O): the only extra residual the flash backward
    # needs beyond lse; (B, H, S) f32 like lse
    delta = (do.astype(jnp.float32) * out.astype(jnp.float32)) \
        .sum(-1).transpose(0, 2, 1)
    kw = dict(causal=causal, window=window, softcap=softcap,
              sm_scale=1.0 / math.sqrt(d), block_q=plan.block_q,
              block_k=plan.block_k, interpret=interpret)
    qp, kp, vp, dop = (_pad_lanes(x) for x in (q, k, v, do))

    if num_valid is None:
        dq, dk, dv = _bwd_call(qp, kp, vp, dop, lse, delta, None, **kw)
    elif _resolve_impl(ragged_impl, interpret) == "rowloop":
        dq, dk, dv = _bwd_rowloop(qp, kp, vp, dop, lse, delta, num_valid,
                                  **kw)
    else:
        dq, dk, dv = _bwd_call(qp, kp, vp, dop, lse, delta, num_valid, **kw)

    dq = dq[..., :d]
    # GQA group-sum: per-q-head dk/dv -> shared kv heads (grad of the
    # index-map head sharing h -> h // rep)
    dk = dk[..., :d].reshape(b, t, hkv, rep, d).sum(3).astype(k.dtype)
    dv = dv[..., :d].reshape(b, t, hkv, rep, d).sum(3).astype(v.dtype)
    return dq, dk, dv
