"""Pallas kernel validation: shape/dtype sweeps vs pure-jnp oracles
(interpret=True executes the kernel bodies on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import attention_ref, flash_attention
from repro.kernels.flash_attention.kernel import tile_plan
from repro.kernels.rglru_scan import rglru_linear_scan, rglru_scan
from repro.kernels.ssd_scan import ssd, ssd_chunked

KEY = jax.random.PRNGKey(42)


# ------------------------------------------------------------ flash attention

FLASH_CASES = [
    # (b, s, t, h, hkv, d, causal, window, softcap, block); block None
    # lets tile_plan choose (tiles wider than 128 on these shapes)
    (2, 128, 128, 4, 4, 64, True, None, None, 64),    # MHA
    (2, 128, 128, 4, 2, 64, True, None, None, 64),    # GQA
    (1, 256, 256, 4, 1, 32, True, None, None, 64),    # MQA
    (1, 256, 256, 4, 2, 64, True, 64, None, 64),      # sliding window
    (2, 128, 128, 2, 2, 64, True, None, 30.0, 64),    # grok-style softcap
    (2, 128, 128, 4, 4, 64, False, None, None, 64),   # bidirectional
    (1, 128, 256, 4, 2, 64, True, None, None, 64),    # q shorter than kv
    (1, 128, 128, 2, 1, 256, True, None, None, 64),   # gemma head_dim 256
    (1, 1024, 1024, 4, 2, 96, True, None, None, None),  # 512 tiles, GQA
    (1, 1024, 1024, 2, 1, 64, True, 200, None, None),   # window band edges
    (1, 512, 1536, 2, 1, 64, True, 300, None, None),    # q shorter, window
]


@pytest.mark.parametrize("case", FLASH_CASES)
def test_flash_attention_matches_ref(case):
    b, s, t, h, hkv, d, causal, window, cap, block = case
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, s, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, t, hkv, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, t, hkv, d), jnp.float32)
    out = flash_attention(q, k, v, causal=causal, window=window, softcap=cap,
                          block_q=block, block_k=block, interpret=True)
    ref = attention_ref(q, k, v, causal=causal, window=window, softcap=cap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtypes(dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 128, 2, 64)).astype(dtype)
    k = jax.random.normal(ks[1], (1, 128, 2, 64)).astype(dtype)
    v = jax.random.normal(ks[2], (1, 128, 2, 64)).astype(dtype)
    out = flash_attention(q, k, v, interpret=True)
    ref = attention_ref(q, k, v)
    assert out.dtype == dtype
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("block", [(32, 64), (64, 32), (128, 128)])
def test_flash_attention_block_shapes(block):
    bq, bk = block
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 128, 2, 64))
    k = jax.random.normal(ks[1], (1, 128, 2, 64))
    v = jax.random.normal(ks[2], (1, 128, 2, 64))
    out = flash_attention(q, k, v, block_q=bq, block_k=bk, interpret=True)
    ref = attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


TILE_PLAN_CASES = [
    # (seq_q, seq_k, window, explicit blocks) -> (block_q, block_k, grid
    # steps, visible tiles) per head
    ((128, 128, None, None), (128, 128, 1, 1)),
    ((384, 384, None, None), (128, 128, 9, 6)),       # 384 = 3 x 128
    ((640, 640, None, None), (128, 128, 25, 15)),     # 640 = 5 x 128
    ((768, 768, None, None), (256, 256, 9, 6)),
    ((4096, 4096, None, None), (512, 512, 64, 36)),   # both cells
    ((512, 1536, None, None), (512, 512, 3, 3)),      # q right-aligned
    ((4096, 4096, 64, None), (128, 128, 1024, 63)),   # window caps the tile
    ((4096, 4096, 200, None), (256, 256, 256, 31)),   # cap rounds up to 256
    ((4096, 4096, 4096, None), (512, 512, 64, 36)),   # wide window: no cap
    ((4096, 4096, None, (32, 64)), (32, 64, 8192, 4160)),  # explicit blocks
    ((128, 128, None, (256, 256)), (128, 128, 1, 1)),  # capped at the seq
    ((64, 64, None, None), (64, 64, 1, 1)),           # shorter than a lane
]


@pytest.mark.parametrize("case", TILE_PLAN_CASES)
def test_tile_plan(case):
    (s, t, window, blocks), want = case
    bq, bk = blocks or (None, None)
    assert tuple(tile_plan(s, t, window=window, block_q=bq,
                           block_k=bk)) == want


def test_flash_attention_rejects_bad_shapes():
    q = jnp.zeros((1, 100, 4, 64))
    k = jnp.zeros((1, 100, 3, 64))
    with pytest.raises(ValueError):
        flash_attention(q, k, k, interpret=True)


# -------------------------------------------------------------------- SSD

SSD_CASES = [
    (2, 64, 4, 16, 8, 16),
    (1, 128, 2, 32, 16, 32),
    (2, 256, 8, 64, 32, 64),
    (1, 128, 64, 64, 128, 64),   # mamba2-1.3b-like head geometry
]


@pytest.mark.parametrize("case", SSD_CASES)
def test_ssd_matches_ref(case):
    b, l, h, p, n, chunk = case
    ks = jax.random.split(KEY, 4)
    x = jax.random.normal(ks[0], (b, l, h, p))
    a = -jnp.abs(jax.random.normal(ks[1], (b, l, h))) * 0.1
    bm = jax.random.normal(ks[2], (b, l, h, n))
    cm = jax.random.normal(ks[3], (b, l, h, n))
    y1, s1 = ssd(x, a, bm, cm, chunk=chunk, interpret=True)
    y2, s2 = ssd_chunked(x, a, bm, cm, chunk)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               atol=5e-4, rtol=5e-4)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               atol=5e-4, rtol=5e-4)


def test_ssd_initial_state_carry():
    """Chunked scan with a carried initial state == one long scan."""
    b, l, h, p, n, chunk = 1, 64, 2, 8, 4, 16
    ks = jax.random.split(KEY, 4)
    x = jax.random.normal(ks[0], (b, l, h, p))
    a = -jnp.abs(jax.random.normal(ks[1], (b, l, h))) * 0.1
    bm = jax.random.normal(ks[2], (b, l, h, n))
    cm = jax.random.normal(ks[3], (b, l, h, n))
    y_full, s_full = ssd(x, a, bm, cm, chunk=chunk, interpret=True)
    half = l // 2
    y1, s1 = ssd(x[:, :half], a[:, :half], bm[:, :half], cm[:, :half],
                 chunk=chunk, interpret=True)
    y2, s2 = ssd(x[:, half:], a[:, half:], bm[:, half:], cm[:, half:],
                 chunk=chunk, initial_state=s1, interpret=True)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)),
                               np.asarray(y_full), atol=5e-4, rtol=5e-4)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s_full),
                               atol=5e-4, rtol=5e-4)


# ------------------------------------------------------------------ RG-LRU

RGLRU_CASES = [(2, 32, 128), (1, 64, 256), (3, 16, 128), (1, 128, 512)]


@pytest.mark.parametrize("case", RGLRU_CASES)
def test_rglru_matches_ref(case):
    b, l, w = case
    ks = jax.random.split(KEY, 3)
    a = jax.nn.sigmoid(jax.random.normal(ks[0], (b, l, w)))
    bx = jax.random.normal(ks[1], (b, l, w))
    h0 = jax.random.normal(ks[2], (b, w))
    h1, hT = rglru_linear_scan(a, bx, h0, interpret=True)
    h2 = rglru_scan(a, bx, initial=h0)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(hT), np.asarray(h2[:, -1]),
                               atol=1e-5, rtol=1e-5)


def test_rglru_no_initial_state():
    ks = jax.random.split(KEY, 2)
    a = jax.nn.sigmoid(jax.random.normal(ks[0], (2, 16, 128)))
    bx = jax.random.normal(ks[1], (2, 16, 128))
    h1, _ = rglru_linear_scan(a, bx, None, interpret=True)
    h2 = rglru_scan(a, bx)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2),
                               atol=1e-5, rtol=1e-5)
