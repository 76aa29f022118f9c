"""Ragged flash-attention: grid-level padding skip + Pallas backward
(DESIGN.md §14).

Properties under test, all in interpret mode (kernel bodies execute on CPU):

  * kernel-path gradients (Pallas forward + Pallas backward) equal the
    masked ``attention_ref`` gradients in fp32 over arbitrary ladder
    buckets and valid counts, INCLUDING ``num_valid == 0`` and
    ``num_valid == bucket``;
  * rows past ``num_valid`` get exact-zero outputs and gradients (never
    garbage — ``0 * NaN`` would poison the trainer's masked reductions);
  * the two ragged lowerings ("grid" = dynamic batch-grid extent,
    "rowloop" = fori_loop over valid rows) agree;
  * the dedicated Pallas backward matches the jnp-oracle recompute
    backward (``bwd_impl="oracle"``) across MHA/GQA/MQA, windows, softcap
    and head dims on both sides of the 128-lane boundary;
  * ``num_valid`` is a traced operand: one executable per bucket shape
    serves every valid count;
  * end to end, ``lm_workload(use_kernel=True)`` reproduces the reference
    workload's loss and parameter gradients on a padded bucket, deriving
    ``num_valid`` from the trainer's suffix mask.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import bucket_ladder
from repro.kernels.flash_attention import (attention_ref, flash_attention,
                                           flash_attention_bwd)
from repro.kernels.flash_attention.ops import attention

KEY = jax.random.PRNGKey(7)

# small fixed geometry for the ragged property sweeps: head_dim 32 keeps
# every case on the lane-padded path (32 < 128 lanes)
S, H, HKV, D = 128, 2, 1, 32
RUNGS = bucket_ladder(12, base=1, growth=1.25, quantum=1)


def _data(b, seed=0, s=S, h=H, hkv=HKV, d=D, t=None):
    ks = jax.random.split(jax.random.fold_in(KEY, seed), 4)
    t = t or s
    return (jax.random.normal(ks[0], (b, s, h, d), jnp.float32),
            jax.random.normal(ks[1], (b, t, hkv, d), jnp.float32),
            jax.random.normal(ks[2], (b, t, hkv, d), jnp.float32),
            jax.random.normal(ks[3], (b, s, h, d), jnp.float32))


def _vg(use_kernel, **kw):
    """value_and_grad of a weighted-sum loss through the attention op."""

    def loss(q, k, v, nv, w):
        out = attention(q, k, v, num_valid=nv, use_kernel=use_kernel,
                        interpret=True, **kw)
        return (out.astype(jnp.float32) * w).sum()

    return jax.value_and_grad(loss, argnums=(0, 1, 2))


# shared jitted steps: the compile cache is reused across examples (and the
# executable-count property below relies on it being per-shape, not per-nv)
KSTEP = jax.jit(_vg(True))
RSTEP = jax.jit(_vg(False))


def _assert_grads_close(ga, gb, atol=5e-4, rtol=5e-3):
    for a, b in zip(ga, gb):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=atol, rtol=rtol)


# ------------------------------------------------------- ragged gradients


@given(st.sampled_from(RUNGS), st.floats(0.0, 1.0))
@settings(max_examples=12, deadline=None)
def test_ragged_grads_match_masked_ref(bucket, frac):
    """Arbitrary (ladder bucket, valid count): kernel == masked reference."""
    nv = int(round(frac * bucket))
    q, k, v, w = _data(bucket, seed=bucket)
    lk, gk = KSTEP(q, k, v, jnp.int32(nv), w)
    lr, gr = RSTEP(q, k, v, jnp.int32(nv), w)
    np.testing.assert_allclose(float(lk), float(lr), atol=5e-3, rtol=5e-4)
    _assert_grads_close(gk, gr)


@pytest.mark.parametrize("bucket", bucket_ladder(16, base=1, growth=1.25,
                                                 quantum=1))
def test_ragged_grad_extremes_every_rung(bucket):
    """num_valid == 0 and == bucket on EVERY rung of a b_max=16 ladder."""
    q, k, v, w = _data(bucket, seed=100 + bucket)
    for nv in (0, bucket):
        lk, gk = KSTEP(q, k, v, jnp.int32(nv), w)
        lr, gr = RSTEP(q, k, v, jnp.int32(nv), w)
        np.testing.assert_allclose(float(lk), float(lr), atol=5e-3,
                                   rtol=5e-4)
        _assert_grads_close(gk, gr)
        if nv == 0:
            assert float(lk) == 0.0
            assert all(not np.any(np.asarray(g)) for g in gk)


def test_padded_rows_exact_zero():
    """Rows >= num_valid: exact-zero output AND gradients, both lowerings.

    Exact zeros, not just small: a padded row carrying NaN/garbage would
    survive multiplication by the loss mask (0 * NaN = NaN)."""
    b, nv = 6, 3
    q, k, v, w = _data(b, seed=3)
    for impl in ("rowloop", "grid"):
        out = flash_attention(q, k, v, num_valid=jnp.int32(nv),
                              ragged_impl=impl, interpret=True)
        assert not np.any(np.asarray(out[nv:])), impl
        _, g = _vg(True, ragged_impl=impl)(q, k, v, jnp.int32(nv), w)
        for grad in g:
            assert np.all(np.isfinite(np.asarray(grad))), impl
            assert not np.any(np.asarray(grad[nv:])), impl


def test_ragged_impls_agree():
    """Dynamic-grid-extent and rowloop lowerings are interchangeable."""
    b, nv = 5, 2
    q, k, v, w = _data(b, seed=4)
    outs, grads = [], []
    for impl in ("rowloop", "grid"):
        l, g = _vg(True, ragged_impl=impl)(q, k, v, jnp.int32(nv), w)
        outs.append(float(l))
        grads.append(g)
    np.testing.assert_allclose(outs[0], outs[1], atol=1e-4, rtol=1e-5)
    _assert_grads_close(grads[0], grads[1], atol=1e-4, rtol=1e-4)


def test_single_executable_serves_all_valid_counts():
    """num_valid is traced data, never a shape: one compile per bucket."""
    f = jax.jit(_vg(True))
    b = 4
    q, k, v, w = _data(b, seed=5)
    for nv in (0, 1, 3, 4):
        f(q, k, v, jnp.int32(nv), w)
    assert f._cache_size() == 1


# -------------------------------------------------------- Pallas backward

BWD_CASES = [
    # (b, s, t, h, hkv, d, causal, window, softcap, valid rows); the later
    # shapes get tiles wider than 128 from tile_plan, so the index maps
    # clamped to the visible band are checked on each of its edges
    (2, 128, 128, 4, 4, 64, True, None, None, 2),    # MHA, whisper head_dim
    (2, 128, 128, 4, 2, 64, True, None, None, 2),    # GQA
    (1, 256, 256, 4, 1, 32, True, None, None, 1),    # MQA, d=32 lane pad
    (1, 256, 256, 4, 2, 64, True, 64, None, 1),      # sliding window
    (2, 128, 128, 2, 2, 64, True, None, 30.0, 2),    # softcap chain rule
    (2, 128, 128, 4, 4, 64, False, None, None, 2),   # bidirectional
    (1, 128, 128, 2, 1, 256, True, None, None, 1),   # full-lane head_dim
    (3, 1024, 1024, 4, 2, 96, True, None, None, 2),  # 512 tiles, GQA, ragged
    (1, 1024, 1024, 2, 1, 64, True, 200, None, 1),   # window band edges
    (1, 512, 1536, 2, 2, 96, True, 300, None, 1),    # q shorter, windowed
]


@pytest.mark.parametrize("case", BWD_CASES)
def test_pallas_bwd_matches_oracle(case):
    """Dedicated backward kernels vs the jnp recompute oracle."""
    b, s, t, h, hkv, d, causal, window, cap, nv = case
    q, k, v, w = _data(b, seed=6, s=s, h=h, hkv=hkv, d=d, t=t)
    kw = dict(causal=causal, window=window, softcap=cap)
    _, gp = _vg(True, bwd_impl="pallas", **kw)(q, k, v, jnp.int32(nv), w)
    _, go = _vg(True, bwd_impl="oracle", **kw)(q, k, v, jnp.int32(nv), w)
    _assert_grads_close(gp, go)
    for g in gp:
        assert not np.any(np.asarray(g[nv:]))


def test_pallas_bwd_matches_oracle_ragged():
    """Both backward impls replicate the ragged zero-row semantics."""
    b, nv = 6, 4
    q, k, v, w = _data(b, seed=8)
    _, gp = _vg(True, bwd_impl="pallas")(q, k, v, jnp.int32(nv), w)
    _, go = _vg(True, bwd_impl="oracle")(q, k, v, jnp.int32(nv), w)
    _assert_grads_close(gp, go)
    for g in (*gp, *go):
        assert not np.any(np.asarray(g[nv:]))


def test_bwd_kernel_direct_residuals():
    """flash_attention_bwd consumes the forward's (out, lse) residuals."""
    b = 2
    q, k, v, w = _data(b, seed=9, h=4, hkv=2, d=64)
    out, lse = flash_attention(q, k, v, interpret=True, return_lse=True)

    def f(q_, k_, v_):
        return attention_ref(q_, k_, v_)

    _, vjp = jax.vjp(f, q, k, v)
    dq_r, dk_r, dv_r = vjp(w)
    dq, dk, dv = flash_attention_bwd(q, k, v, w, out, lse, interpret=True)
    _assert_grads_close((dq, dk, dv), (dq_r, dk_r, dv_r))


# -------------------------------------------------- lane padding (d < 128)


@pytest.mark.parametrize("d", [32, 64])
def test_lane_padded_head_dims(d):
    """head_dim < 128 is zero-padded to the lane width inside the wrapper;
    the padded lanes must be provably inert in outputs and grads."""
    q, k, v, w = _data(2, seed=10 + d, h=4, hkv=2, d=d)
    out = flash_attention(q, k, v, interpret=True)
    ref = attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5,
                               rtol=2e-5)
    _, gk = _vg(True)(q, k, v, jnp.int32(2), w)
    _, gr = _vg(False)(q, k, v, jnp.int32(2), w)
    _assert_grads_close(gk, gr)


# ------------------------------------------------------- workload wiring


@pytest.mark.slow
def test_lm_workload_kernel_matches_reference():
    """lm_workload(use_kernel=True) derives num_valid from the trainer's
    suffix mask; loss and parameter grads must match the reference path on
    a padded bucket (train/mesh.py suffix-padding contract)."""
    import jax.flatten_util

    from repro.api import lm_workload
    from repro.configs import get_config
    from repro.data import DataPipeline
    from repro.models import reduced

    bucket, valid = 4, 3
    cfg = reduced(get_config("gemma-2b"))
    pipe = DataPipeline(cfg, seq_len=128, num_workers=1, seed=0)
    batch = pipe.next_batch(0, bucket)
    mask = (jnp.arange(bucket) < valid).astype(jnp.float32)

    results = {}
    for use_kernel in (False, True):
        wl = lm_workload(cfg, pipe, use_kernel=use_kernel)
        params = wl.init(jax.random.PRNGKey(0))
        (ls, ws, _aux), g = wl.loss_and_grad(params, batch, mask)
        flat, _ = jax.flatten_util.ravel_pytree(g)
        results[use_kernel] = (float(ls), float(ws), np.asarray(flat))

    assert results[True][0] == pytest.approx(results[False][0], rel=1e-5)
    assert results[True][1] == results[False][1]
    scale = np.max(np.abs(results[False][2])) or 1.0
    np.testing.assert_allclose(results[True][2], results[False][2],
                               atol=2e-3 * scale, rtol=5e-3)
