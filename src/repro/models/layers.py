"""Neural-net building blocks shared by all architecture families.

Pure-functional JAX: every block is (init_fn, apply_fn)-style with explicit
parameter pytrees (nested dicts), so the launch layer can attach
PartitionSpecs by walking the same tree structure.

All attention variants support two modes:
  * full-sequence (training / prefill): x is (B, S, D);
  * single-token decode: x is (B, 1, D) plus a KV cache and a position.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig, Rope

# --------------------------------------------------------------------- init


def _dense_init(key, shape, dtype, scale: Optional[float] = None):
    fan_in = shape[0] if len(shape) > 1 else 1
    std = scale if scale is not None else 1.0 / math.sqrt(max(fan_in, 1))
    return (jax.random.normal(key, shape) * std).astype(dtype)


def init_linear(key, d_in, d_out, cfg: ModelConfig, use_bias=None):
    use_bias = cfg.use_bias if use_bias is None else use_bias
    p = {"w": _dense_init(key, (d_in, d_out), cfg.p_dtype)}
    if use_bias:
        p["b"] = jnp.zeros((d_out,), cfg.p_dtype)
    return p


def linear(p, x):
    y = x @ p["w"].astype(x.dtype)
    if "b" in p:
        y = y + p["b"].astype(x.dtype)
    return y


# --------------------------------------------------------------------- norms


def init_norm(cfg: ModelConfig, d=None):
    d = d or cfg.d_model
    p = {"scale": jnp.ones((d,), cfg.p_dtype)}
    if cfg.norm == "layernorm":
        p["bias"] = jnp.zeros((d,), cfg.p_dtype)
    return p


def apply_norm(p, x, cfg: ModelConfig):
    """Normalization with f32 *statistics* but dtype-preserving tensor math.

    Upcasting the whole (B,S,D) tensor to f32 puts two full-size converts
    (and their f32 vjp cotangents) on the HBM path per norm — measured as
    the dominant §Roofline memory term for train shapes (§Perf iteration
    T3). Only the per-row statistics are f32; the elementwise scaling stays
    in the residual dtype, as production TPU stacks do."""
    dt = x.dtype
    xf = x.astype(jnp.float32)  # fuses into the reduction, not materialized
    if cfg.norm == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        inv = jax.lax.rsqrt(var + cfg.norm_eps)
        y = (x - mu.astype(dt)) * inv.astype(dt)
        y = y * p["scale"].astype(dt) + p["bias"].astype(dt)
    else:  # rmsnorm
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        inv = jax.lax.rsqrt(ms + cfg.norm_eps)
        y = x * inv.astype(dt) * p["scale"].astype(dt)
    return y


# ---------------------------------------------------------------------- RoPE


def rope_frequencies(head_dim: int, spec: Rope):
    """(inverse frequencies (head_dim/2,) float32, attention factor) of a
    rotary embedding.

    Plain rope: theta^(-2i/head_dim), factor 1.  YaRN (Hugging Face's
    ``_compute_yarn_parameters``): the correction range is where
    ``beta_fast`` and ``beta_slow`` rotations fit into the original
    length, dim * ln(L / (2 pi beta)) / (2 ln theta), floored and ceiled;
    over it a linear ramp blends the extrapolated frequencies (below the
    range) into the interpolated ones (theta^(-2i/dim) / factor, above)."""
    half = head_dim // 2
    inv = spec.theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    if spec.yarn_factor is None:
        return inv, 1.0

    def correction_dim(rotations):
        return (head_dim * math.log(
            spec.yarn_original_len / (rotations * 2 * math.pi))
            / (2 * math.log(spec.theta)))

    low = max(math.floor(correction_dim(spec.yarn_beta_fast)), 0)
    high = min(math.ceil(correction_dim(spec.yarn_beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    inv = inv / spec.yarn_factor * ramp + inv * (1.0 - ramp)
    factor = spec.yarn_attention_factor
    if factor is None:
        factor = 0.1 * math.log(spec.yarn_factor) + 1.0
    return inv, factor


def rope(x, positions, theta):
    """Rotary embedding, *interleaved* (GPT-J) pair layout.

    x: (..., S, H, Dh) with even Dh; positions: (..., S) int32; theta: the
    base, or a :class:`Rope` (YaRN where it says so; its attention factor
    scales cos and sin, so attention downstream is unchanged).

    Interleaved pairs (2i, 2i+1) rather than NeoX half-rotation: the
    rotation is then elementwise within any even-sized shard of Dh, so a
    head_dim-sharded KV cache needs NO resharding around rope (the NeoX
    concat across Dh halves forced GSPMD to all-gather the f32 cache every
    decode step — §Perf iteration D2). Attention scores are identical
    (same set of 2D rotations, permuted frequency assignment, applied
    consistently to q and k).
    """
    spec = theta if isinstance(theta, Rope) else Rope(theta)
    dh = x.shape[-1]
    half = dh // 2
    freqs, factor = rope_frequencies(dh, spec)
    ang = positions[..., :, None].astype(jnp.float32) * freqs  # (..., S, half)
    cos = factor * jnp.cos(ang)[..., :, None, :]  # (..., S, 1, half)
    sin = factor * jnp.sin(ang)[..., :, None, :]
    xr = x.astype(jnp.float32).reshape(x.shape[:-1] + (half, 2))
    x1, x2 = xr[..., 0], xr[..., 1]
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    return jnp.stack([y1, y2], axis=-1).reshape(x.shape).astype(x.dtype)


# ----------------------------------------------------------------- attention


def _softcap(logits, cap: Optional[float]):
    if cap is None:
        return logits
    return cap * jnp.tanh(logits / cap)


def attention_scores(q, k, v, mask, softcap=None, upcast=True):
    """q: (B,S,H,Dqk), k: (B,T,Hkv,Dqk), v: (B,T,Hkv,Dv), H % Hkv == 0.

    mask: (S,T) or (B,1,S,T) boolean. Dv may differ from Dqk (MLA).
    upcast=False keeps K/V in their storage dtype with f32 *accumulation*
    (preferred_element_type) — the MXU does bf16 x bf16 -> f32 natively, and
    a materialized f32 copy of a decode KV cache is exactly what GSPMD then
    reshards at full-cache cost (§Perf iteration D2)."""
    b, s, h, dh = q.shape
    hkv = k.shape[2]
    dv = v.shape[-1]
    rep = h // hkv
    out_dtype = q.dtype
    if upcast:
        q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    qg = q.reshape(b, s, hkv, rep, dh)
    logits = jnp.einsum("bsgrd,btgd->bgrst", qg, k,
                        preferred_element_type=jnp.float32) / math.sqrt(dh)
    logits = _softcap(logits, softcap)
    if mask.ndim == 2:
        mask = mask[None, None, None]
    else:  # (B,1,S,T) -> (B,1,1,S,T)
        mask = mask[:, :, None]
    logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bgrst,btgd->bsgrd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, s, h, dv).astype(out_dtype)


def chunked_attention_scores(q, k, v, *, causal=True, window=None,
                             softcap=None, chunk=512):
    """Flash-style online-softmax attention in pure jnp (§Perf iteration T1).

    Scans over key/value chunks carrying (m, l, acc); only (S x chunk) score
    tiles ever materialize, never the (S x T) matrix — the jnp analogue of
    the Pallas flash kernel, visible to XLA's memory/bytes analysis on the
    dry-run. Semantics identical to attention_scores (same mask args).
    """
    b, s, h, dh = q.shape
    t, hkv = k.shape[1], k.shape[2]
    rep = h // hkv
    chunk = min(chunk, t)
    assert t % chunk == 0, (t, chunk)
    nc = t // chunk
    qg = (q.astype(jnp.float32) / math.sqrt(dh)).reshape(b, s, hkv, rep, dh)
    kc = k.astype(jnp.float32).reshape(b, nc, chunk, hkv, dh)
    vc = v.astype(jnp.float32).reshape(b, nc, chunk, hkv, dh)
    q_pos = jnp.arange(s) + (t - s)

    def body(carry, xs):
        m_prev, l_prev, acc = carry
        kb, vb, ci = xs
        logits = jnp.einsum("bsgrd,bcgd->bgrsc", qg, kb)
        logits = _softcap(logits, softcap)
        k_pos = ci * chunk + jnp.arange(chunk)
        mask = jnp.ones((s, chunk), bool)
        if causal:
            mask = mask & (k_pos[None, :] <= q_pos[:, None])
        if window is not None:
            mask = mask & (k_pos[None, :] > q_pos[:, None] - window)
        logits = jnp.where(mask[None, None, None], logits, -1e30)
        m_cur = jnp.maximum(m_prev, logits.max(-1))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(logits - m_cur[..., None])
        l_cur = l_prev * alpha + p.sum(-1)
        acc = acc * alpha[..., None] + jnp.einsum("bgrsc,bcgd->bgrsd", p, vb)
        return (m_cur, l_cur, acc), None

    m0 = jnp.full((b, hkv, rep, s), -1e30)
    l0 = jnp.zeros((b, hkv, rep, s))
    a0 = jnp.zeros((b, hkv, rep, s, dh))
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0),
        (kc.transpose(1, 0, 2, 3, 4), vc.transpose(1, 0, 2, 3, 4),
         jnp.arange(nc)))
    out = acc / jnp.maximum(l, 1e-20)[..., None]
    return out.transpose(0, 3, 1, 2, 4).reshape(b, s, h, dh).astype(q.dtype)


def causal_mask(s: int, t: int, offset: int = 0, window: Optional[int] = None):
    """(s, t) boolean mask; query i is at absolute position offset + i."""
    qi = offset + jnp.arange(s)[:, None]
    kj = jnp.arange(t)[None, :]
    m = kj <= qi
    if window is not None:
        m = m & (kj > qi - window)
    return m


def init_gqa(key, cfg: ModelConfig, d_model=None, num_heads=None, num_kv=None,
             head_dim=None, use_bias=None):
    d = d_model or cfg.d_model
    h = num_heads or cfg.num_heads
    hkv = num_kv or cfg.num_kv_heads
    dh = head_dim or cfg.head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": init_linear(ks[0], d, h * dh, cfg, use_bias),
        "wk": init_linear(ks[1], d, hkv * dh, cfg, use_bias),
        "wv": init_linear(ks[2], d, hkv * dh, cfg, use_bias),
        "wo": init_linear(ks[3], h * dh, d, cfg, use_bias),
    }


def gqa_attention(p, x, cfg: ModelConfig, *, positions=None, cache=None,
                  window=None, use_rope=True, cross_kv=None, softcap=None,
                  causal=True, num_valid=None, rope_spec=None):
    """GQA/MQA/MHA self- or cross-attention with optional KV cache.

    cache: None, or dict {k: (B, T, Hkv, Dh), v: ..., idx: ()} — decode mode
    writes x's projections at position idx and attends over the cache.
    cross_kv: precomputed (k, v) for encoder-decoder cross attention.
    num_valid: optional traced int32 valid-row count for bucket-padded
    batches — only honored on the Pallas kernel path (training/prefill),
    where padded rows are grid-skipped instead of merely loss-masked
    (DESIGN.md §14); other paths compute padded rows and rely on the loss
    mask as before.  rope_spec: the layer kind's :class:`Rope` (None =
    plain rope at ``cfg.rope_theta``).  Returns (out, new_cache).
    """
    b, s, d = x.shape
    # infer head counts from param shapes (works for reduced configs too)
    dh = cfg.head_dim
    nh = p["wq"]["w"].shape[1] // dh
    nkv = p["wk"]["w"].shape[1] // dh
    rope_spec = rope_spec or Rope(cfg.rope_theta)

    q = linear(p["wq"], x).reshape(b, s, nh, dh)
    if positions is None:
        positions = jnp.arange(s)[None, :]

    if cross_kv is not None:
        k, v = cross_kv
        if use_rope:
            q = rope(q, positions, rope_spec)
        t = k.shape[1]
        mask = jnp.ones((s, t), dtype=bool)
        out = attention_scores(q, k, v, mask, softcap)
        return linear(p["wo"], out.reshape(b, s, nh * dh)), cache

    k = linear(p["wk"], x).reshape(b, s, nkv, dh)
    v = linear(p["wv"], x).reshape(b, s, nkv, dh)
    if use_rope:
        q = rope(q, positions, rope_spec)
        k = rope(k, positions, rope_spec)

    if cache is None:
        if cfg.use_pallas and causal and s % 128 == 0:
            from repro.kernels.flash_attention.ops import attention

            out = attention(
                q, k, v, causal=True, window=window, softcap=softcap,
                num_valid=num_valid,
                interpret=jax.default_backend() == "cpu")
        elif cfg.attn_chunk is not None and s % min(cfg.attn_chunk, s) == 0:
            out = chunked_attention_scores(
                q, k, v, causal=causal, window=window, softcap=softcap,
                chunk=cfg.attn_chunk)
        else:
            mask = causal_mask(s, s, 0, window) if causal else jnp.ones(
                (s, s), bool)
            out = attention_scores(q, k, v, mask, softcap)
        new_cache = None
    else:
        idx = cache["idx"]
        t = cache["k"].shape[1]
        from repro.models import sharded_attn
        from repro.models.shard_hooks import get_rules

        mesh_info = get_rules().get("decode_attn")
        if s == 1 and sharded_attn.applicable(cfg, b, dh, mesh_info):
            out, ck, cv = sharded_attn.decode_attention(
                q, k, v, cache["k"], cache["v"], idx, mesh_info=mesh_info,
                softcap=softcap)
        else:
            ck = _rowwise_dus(cache["k"], k, idx)
            cv = _rowwise_dus(cache["v"], v, idx)
            # mask: attend to slots holding positions <= idx (ring for window)
            n_written = jnp.minimum(idx + 1, t)          # (B,) incl. current
            valid = jnp.arange(t)[None, :] < n_written[:, None]   # (B, t)
            mask = jnp.broadcast_to(valid[:, None, None, :], (b, 1, s, t))
            out = attention_scores(q, ck, cv, mask, softcap, upcast=False)
        new_cache = {"k": ck, "v": cv, "idx": idx + s}
    return linear(p["wo"], out.reshape(b, s, nh * dh)), new_cache


def init_attn_cache(cfg: ModelConfig, batch: int, length: int, dtype,
                    num_kv=None, head_dim=None):
    nkv = num_kv or cfg.num_kv_heads
    dh = head_dim or cfg.head_dim
    return {
        "k": jnp.zeros((batch, length, nkv, dh), dtype),
        "v": jnp.zeros((batch, length, nkv, dh), dtype),
        # per-ROW write positions: continuous batching decodes sequences at
        # different offsets in the same compiled step
        "idx": jnp.zeros((batch,), jnp.int32),
    }


def _rowwise_dus(cache, update, idx):
    """Per-row dynamic_update_slice: cache (B,T,...), update (B,s,...),
    idx (B,) — lowers to an efficient scatter. B==1 (long-context decode)
    keeps the cheaper plain DUS."""
    t = cache.shape[1]
    if cache.shape[0] == 1:
        return jax.lax.dynamic_update_slice(
            cache, update.astype(cache.dtype),
            (0, idx[0] % t) + (0,) * (cache.ndim - 2))
    return jax.vmap(
        lambda c, u, i: jax.lax.dynamic_update_slice(
            c, u.astype(c.dtype), (i % t,) + (0,) * (c.ndim - 1))
    )(cache, update, idx)


# ------------------------------------------------------------------- MLA


def init_mla(key, cfg: ModelConfig):
    """DeepSeek-V2 Multi-head Latent Attention."""
    ks = jax.random.split(key, 6)
    nh = cfg.num_heads
    qk = cfg.qk_nope_dim + cfg.qk_rope_dim
    p = {
        "wq_a": init_linear(ks[0], cfg.d_model, cfg.q_lora_rank, cfg, False),
        "q_norm": init_norm(cfg, cfg.q_lora_rank),
        "wq_b": init_linear(ks[1], cfg.q_lora_rank, nh * qk, cfg, False),
        # kv_a projects to compressed latent + shared rope key
        "wkv_a": init_linear(ks[2], cfg.d_model,
                             cfg.kv_lora_rank + cfg.qk_rope_dim, cfg, False),
        "kv_norm": init_norm(cfg, cfg.kv_lora_rank),
        "wkv_b": init_linear(ks[3], cfg.kv_lora_rank,
                             nh * (cfg.qk_nope_dim + cfg.v_head_dim), cfg, False),
        "wo": init_linear(ks[4], nh * cfg.v_head_dim, cfg.d_model, cfg, False),
    }
    return p


def mla_attention(p, x, cfg: ModelConfig, *, positions=None, cache=None,
                  window=None):
    """MLA: queries from a low-rank q latent; K/V from a compressed KV latent
    plus one shared rotary key. The cache stores only (c_kv, k_rope) —
    the memory saving that is MLA's point."""
    b, s, _ = x.shape
    nh = cfg.num_heads
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    if positions is None:
        positions = jnp.arange(s)[None, :]

    q = linear(p["wq_b"], apply_norm(p["q_norm"], linear(p["wq_a"], x), cfg))
    q = q.reshape(b, s, nh, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = rope(q_rope, positions, cfg.rope_theta)

    kv_a = linear(p["wkv_a"], x)
    c_kv, k_rope = kv_a[..., : cfg.kv_lora_rank], kv_a[..., cfg.kv_lora_rank:]
    c_kv = apply_norm(p["kv_norm"], c_kv, cfg)
    k_rope = rope(k_rope.reshape(b, s, 1, dr), positions, cfg.rope_theta)

    if cache is not None:
        # ---- decode: absorbed-weight MLA (DeepSeek-V2 §inference) ----
        # Never expand the latent cache to per-head K/V (that would build a
        # (B, T, H, dn+dv) tensor — 274 TB for deepseek-v2 x decode_32k).
        # Instead fold wkv_b into the query/output sides and attend directly
        # in the rank-`kv_lora` latent space (§Perf iteration D1).
        idx = cache["idx"]
        t = cache["c_kv"].shape[1]
        w_b = p["wkv_b"]["w"].reshape(cfg.kv_lora_rank, nh, dn + dv)
        w_uk, w_uv = w_b[..., :dn], w_b[..., dn:]
        q_eff = jnp.einsum("bshd,rhd->bshr", q_nope, w_uk.astype(q_nope.dtype),
                           preferred_element_type=jnp.float32).astype(x.dtype)
        sm_scale = 1.0 / math.sqrt(dn + dr)

        from repro.models import sharded_attn
        from repro.models.shard_hooks import get_rules

        mesh_info = get_rules().get("decode_attn")
        if s == 1 and sharded_attn.mla_applicable(cfg, b, mesh_info):
            out_lat, c_all, kr_all = sharded_attn.mla_decode_attention(
                q_eff, q_rope, c_kv, k_rope, cache["c_kv"], cache["k_rope"],
                idx, mesh_info=mesh_info, sm_scale=sm_scale)
        else:
            c_all = _rowwise_dus(cache["c_kv"], c_kv, idx)
            kr_all = _rowwise_dus(cache["k_rope"], k_rope, idx)
            n_written = jnp.minimum(idx + 1, t)             # (B,)
            mask = jnp.arange(t)[None, :] < n_written[:, None]  # (B, t)
            logits = (jnp.einsum("bshr,btr->bhst", q_eff, c_all,
                                 preferred_element_type=jnp.float32)
                      + jnp.einsum("bshd,btd->bhst", q_rope, kr_all[:, :, 0],
                                   preferred_element_type=jnp.float32))
            logits = logits * sm_scale
            logits = jnp.where(mask[:, None, None, :], logits, -1e30)
            probs = jax.nn.softmax(logits, axis=-1)
            out_lat = jnp.einsum("bhst,btr->bshr", probs.astype(c_all.dtype),
                                 c_all, preferred_element_type=jnp.float32
                                 ).astype(x.dtype)
        new_cache = {"c_kv": c_all, "k_rope": kr_all, "idx": idx + s}
        out = jnp.einsum("bshr,rhd->bshd", out_lat, w_uv.astype(out_lat.dtype),
                         preferred_element_type=jnp.float32).astype(x.dtype)
        return linear(p["wo"], out.reshape(b, s, nh * dv)), new_cache

    # ---- prefill/train: standard (FLOPs-optimal) expanded formulation ----
    t = s
    c_all, kr_all = c_kv, k_rope
    mask = causal_mask(s, s, 0, window)
    kv = linear(p["wkv_b"], c_all).reshape(b, t, nh, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(kr_all, (b, t, nh, dr)).astype(k_nope.dtype)],
        axis=-1)
    qq = jnp.concatenate([q_nope, q_rope], axis=-1)
    out = attention_scores(qq, k, v, mask)
    return linear(p["wo"], out.reshape(b, s, nh * dv)), None


def init_mla_cache(cfg: ModelConfig, batch: int, length: int, dtype):
    return {
        "c_kv": jnp.zeros((batch, length, cfg.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, length, 1, cfg.qk_rope_dim), dtype),
        "idx": jnp.zeros((batch,), jnp.int32),
    }


# --------------------------------------------------------------------- MLPs


def init_mlp(key, cfg: ModelConfig, d_ff=None):
    d_ff = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.mlp in ("swiglu", "geglu"):
        return {
            "w_gate": init_linear(ks[0], cfg.d_model, d_ff, cfg),
            "w_up": init_linear(ks[1], cfg.d_model, d_ff, cfg),
            "w_down": init_linear(ks[2], d_ff, cfg.d_model, cfg),
        }
    return {  # plain gelu (whisper)
        "w_up": init_linear(ks[0], cfg.d_model, d_ff, cfg),
        "w_down": init_linear(ks[1], d_ff, cfg.d_model, cfg),
    }


def apply_mlp(p, x, cfg: ModelConfig):
    if "w_gate" in p:
        act = jax.nn.silu if cfg.mlp == "swiglu" else (
            lambda v: jax.nn.gelu(v, approximate=True))
        return linear(p["w_down"], act(linear(p["w_gate"], x)) * linear(p["w_up"], x))
    return linear(p["w_down"], jax.nn.gelu(linear(p["w_up"], x), approximate=True))


# ---------------------------------------------------------------------- MoE


def init_moe(key, cfg: ModelConfig):
    """The router over all ``num_experts`` and the experts held here: all
    of them on the one-hot path, the first ``experts_held`` on the
    held-expert layer."""
    e, d, f = cfg.num_experts, cfg.d_model, cfg.moe_d_ff or cfg.d_ff
    held = e if cfg.experts_held is None else cfg.experts_held
    ks = jax.random.split(key, 5)
    std = 1.0 / math.sqrt(d)
    p = {
        "router": {"w": _dense_init(ks[0], (d, e), jnp.float32)},
        "w_gate": _dense_init(ks[1], (held, d, f), cfg.p_dtype, std),
        "w_up": _dense_init(ks[2], (held, d, f), cfg.p_dtype, std),
        "w_down": _dense_init(ks[3], (held, f, d), cfg.p_dtype,
                              1.0 / math.sqrt(f)),
    }
    if cfg.num_shared_experts:
        fs = f * cfg.num_shared_experts
        p["shared"] = init_mlp(ks[4], cfg.with_(mlp="swiglu"), d_ff=fs)
    return p


def moe_capacity(group_size: int, top_k: int, num_experts: int, factor: float) -> int:
    c = int(math.ceil(group_size * top_k * factor / num_experts))
    return max(c, 1)


def apply_moe(p, x, cfg: ModelConfig):
    """GShard-style top-k MoE with one-hot dispatch (TPU/MXU-friendly).

    x: (B, S, D). Tokens are processed in groups of `moe_group_size`; each
    group dispatches to per-expert capacity buffers via a one-hot einsum.
    Returns (out, aux_loss).
    """
    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.moe_top_k
    g = min(cfg.moe_group_size, b * s)
    tokens = x.reshape(-1, d)
    t = tokens.shape[0]
    pad = (-t) % g
    if pad:
        tokens = jnp.pad(tokens, ((0, pad), (0, 0)))
    ng = tokens.shape[0] // g
    xt = tokens.reshape(ng, g, d)

    logits = xt.astype(jnp.float32) @ p["router"]["w"]          # (ng, g, e)
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(probs, k)                         # (ng, g, k)
    topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)

    cap = moe_capacity(g, k, e, cfg.moe_capacity_factor)
    # one-hot expert assignment per (token, choice): (ng, g, k, e)
    sel = jax.nn.one_hot(topi, e, dtype=jnp.float32)
    # position of each (token, choice) within its expert buffer
    # cumulative count over (g, k) flattened in token-major order
    sel_flat = sel.reshape(ng, g * k, e)
    pos = jnp.cumsum(sel_flat, axis=1) - sel_flat                # (ng, g*k, e)
    pos = (pos * sel_flat).sum(-1).reshape(ng, g, k)             # (ng, g, k)
    fits = pos < cap
    gate = topv * fits                                           # dropped tokens get 0
    # dispatch tensor (ng, g, e, cap)
    pos_oh = jax.nn.one_hot(pos, cap, dtype=jnp.float32)         # (ng, g, k, cap)
    dispatch = jnp.einsum("ngke,ngkc->ngec", sel * fits[..., None], pos_oh)
    combine = jnp.einsum("ngke,ngkc,ngk->ngec", sel, pos_oh, gate)

    xin = jnp.einsum("ngd,ngec->necd", xt, dispatch.astype(xt.dtype))  # (ng,e,cap,d)
    act = jax.nn.silu(jnp.einsum("necd,edf->necf", xin, p["w_gate"].astype(xt.dtype)))
    up = jnp.einsum("necd,edf->necf", xin, p["w_up"].astype(xt.dtype))
    xout = jnp.einsum("necf,efd->necd", act * up, p["w_down"].astype(xt.dtype))
    out = jnp.einsum("necd,ngec->ngd", xout, combine.astype(xt.dtype))
    out = out.reshape(-1, d)[:t].reshape(b, s, d)

    # Switch-style load-balance aux loss
    me = probs.mean(axis=1)                                      # (ng, e)
    ce = sel.sum(2).mean(axis=1)                                 # fraction routed
    aux = (me * ce).sum(-1).mean() * e

    if "shared" in p:
        out = out + apply_mlp(p["shared"], x, cfg.with_(mlp="swiglu"))
    return out, aux


def held_rungs(group: int, top_k: int, held: int, num_experts: int):
    """The row counts a token group's pair buffer may take, smallest
    first: twice, four times, ... the pairs a group fills on average
    (group x top_k x held / num_experts), each rounded up to 8 rows and,
    past one kernel row tile, to whole tiles, then the most a group can
    fill, group x min(top_k, held).  Where twice the average is already
    that most, the one rung is it."""
    from repro.kernels.grouped_matmul.ops import MAX_TM

    rows = group * min(top_k, held)
    rungs, m = [], 2
    while True:
        r = -(-m * group * top_k * held // num_experts)
        r = -(-r // 8) * 8
        if r > MAX_TM:
            r = -(-r // MAX_TM) * MAX_TM
        if r >= rows:
            return tuple(rungs) + (rows,)
        if not rungs or r > rungs[-1]:
            rungs.append(r)
        m *= 2


def _pairs_at(rows, kernel, ws, xt, tok, gate, sizes):
    """One group's result from the held experts, its pairs in a buffer of
    ``rows`` rows: gathered, SwiGLU as three grouped matmuls, scaled by
    their gates (zero past the filled rows) and added to their tokens."""
    from repro.kernels.grouped_matmul import grouped_matmul

    use_kernel, interpret = kernel
    w_gate, w_up, w_down = ws
    tok, gate = tok[:rows], gate[:rows]
    xin = xt[tok]
    mm = dict(use_kernel=use_kernel, interpret=interpret)
    h = grouped_matmul(xin, w_gate, sizes, **mm)
    u = grouped_matmul(xin, w_up, sizes, **mm)
    y = grouped_matmul(jax.nn.silu(h) * u, w_down, sizes, **mm)
    return jnp.zeros_like(xt).at[tok].add(y * gate.astype(y.dtype)[:, None])


def _branches(rungs, kernel, fn):
    return [lambda *a, r=r: fn(r, kernel, *a) for r in rungs]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _held_pairs(rungs, kernel, rung, ws, xt, tok, gate, sizes):
    """``_pairs_at`` at ``rungs[rung]`` rows.  Its gradient is taken one
    rung at a time: the backward recomputes the taken rung's forward and
    applies its VJP, where JAX's own linearisation of the ``switch``
    would keep every rung's residuals, the unused ones zero-filled."""
    return jax.lax.switch(rung, _branches(rungs, kernel, _pairs_at),
                          ws, xt, tok, gate, sizes)


def _held_pairs_fwd(rungs, kernel, rung, ws, xt, tok, gate, sizes):
    return (_held_pairs(rungs, kernel, rung, ws, xt, tok, gate, sizes),
            (rung, ws, xt, tok, gate, sizes))


def _pairs_vjp(rows, kernel, ws, xt, tok, gate, sizes, d_out):
    # checkpointed, so that the kernels keep their op names (``gmm``,
    # ``tgmm``) in a device trace: a plain ``jax.vjp`` prefixes them with
    # its transforms; the primal it computes first goes unused and is cut
    _, vjp = jax.vjp(jax.checkpoint(
        lambda w, x, g: _pairs_at(rows, kernel, w, x, tok, g, sizes)),
        ws, xt, gate)
    return vjp(d_out)


def _held_pairs_bwd(rungs, kernel, res, d_out):
    rung, ws, xt, tok, gate, sizes = res
    d_ws, d_xt, d_gate = jax.lax.switch(
        rung, _branches(rungs, kernel, _pairs_vjp),
        ws, xt, tok, gate, sizes, d_out)
    return None, d_ws, d_xt, None, d_gate, None


_held_pairs.defvjp(_held_pairs_fwd, _held_pairs_bwd)


# jitted, so that every layer and bucket of the same shapes shares one
# trace and its derivatives: traced once a layer, the ladder's branches
# made each worker step's set-up seconds longer
@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _route_group(k, held, rungs, kernel, router, ws, xt, vt):
    """One group of tokens through the held-expert layer: its result, the
    pairs each held expert took, the router's probabilities and choices
    summed over the valid tokens, the pairs filled and whether they took
    the largest rung."""
    # the router in float32 at HIGHEST: a rounding flip near a tie would
    # change a token's experts
    logits = jnp.dot(xt.astype(jnp.float32), router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, experts = jax.lax.top_k(probs, k)
    gates = gates / gates.sum(-1, keepdims=True)
    expert, gate = experts.reshape(-1), gates.reshape(-1)
    key = jnp.where((expert < held) & jnp.repeat(vt, k), expert, held)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.bincount(key, length=held + 1)[:held].astype(jnp.int32)
    filled = sizes.sum()
    rung = (filled > jnp.array(rungs[:-1], jnp.int32)).sum()
    gate = jnp.where(jnp.arange(gate.shape[0]) < filled, gate[order], 0.0)
    out = _held_pairs(rungs, kernel, rung, ws, xt, order // k, gate, sizes)
    vf = vt.astype(jnp.float32)[:, None]
    chosen = jax.nn.one_hot(experts, router.shape[1], dtype=jnp.float32
                            ).sum(1)
    return (out, sizes, (probs * vf).sum(0), (chosen * vf).sum(0), filled,
            rung == len(rungs) - 1)


def apply_held_moe(p, x, cfg: ModelConfig, num_valid=None):
    """The expert layer of one chip of an expert-parallel deployment: it
    routes over all ``num_experts`` and computes only the part of the
    result that its ``experts_held`` (the first ones) give, dropping no
    token.  With every expert held it is the whole layer.

    Tokens go ``moe_group_size`` at a time through a scan.  A group's
    (token, choice) pairs on held experts, sorted by expert, fill the
    first rows of a buffer whose size is the smallest of ``held_rungs``
    that holds them (the largest is the most a group can fill); SwiGLU
    runs on them as grouped matmuls (``kernels.grouped_matmul``), and the
    products, scaled by their gates, are added back to their tokens.
    A group is checkpointed: the backward keeps its input alone, routes
    it again and recomputes the rung it took (``_held_pairs``).  Rows at
    or past ``num_valid`` (bucket padding) route nowhere.

    x: (B, S, D).  Returns (out, aux, counters): the Switch balance loss
    over the router, ``expert_pairs`` (pairs the held experts computed),
    ``expert_pairs_max`` (the largest held expert's load),
    ``expert_rows_max`` (the most pairs a group filled) and
    ``expert_groups_full`` (groups that took the largest rung; with one
    rung, every group).
    """
    b, s, d = x.shape
    e, k, held = cfg.num_experts, cfg.moe_top_k, cfg.experts_held
    t = b * s
    g = min(cfg.moe_group_size, t)
    pad = (-t) % g
    rungs = held_rungs(g, k, held, e)
    tokens = jnp.pad(x.reshape(t, d), ((0, pad), (0, 0)))
    row_of = jnp.arange(t + pad) // s
    valid = (row_of < (b if num_valid is None else num_valid)) & (
        jnp.arange(t + pad) < t)
    kernel = (cfg.use_pallas, jax.default_backend() == "cpu")
    ws = tuple(p[n].astype(x.dtype) for n in ("w_gate", "w_up", "w_down"))

    group = jax.checkpoint(functools.partial(
        _route_group, k, held, rungs, kernel, p["router"]["w"], ws))
    out, sizes, p_sum, c_sum, filled, full = jax.lax.map(
        lambda a: group(*a), (tokens.reshape(-1, g, d), valid.reshape(-1, g)))
    out = out.reshape(-1, d)[:t].reshape(b, s, d)
    n = jnp.maximum(valid.sum(), 1).astype(jnp.float32)
    aux = (p_sum.sum(0) / n * c_sum.sum(0) / n).sum() * e
    load = sizes.sum(0)
    return out, aux, {"expert_pairs": load.sum(),
                      "expert_pairs_max": load.max(),
                      "expert_rows_max": filled.max(),
                      "expert_groups_full": full.sum(dtype=jnp.int32)}


# ----------------------------------------------------------- embeddings etc.


def init_embedding(key, cfg: ModelConfig):
    return {"table": _dense_init(key, (cfg.vocab_size, cfg.d_model), cfg.p_dtype, 1.0)}


def embed(p, tokens, cfg: ModelConfig):
    x = p["table"][tokens].astype(cfg.act_dtype)
    if cfg.scale_embeddings:
        x = x * math.sqrt(cfg.d_model)
    return x


def unembed(p_embed, p_head, x, cfg: ModelConfig):
    """Logits in the activation dtype (bf16 on TPU — the f32 (B,S,V) tensor
    would dominate big-vocab memory; the loss upcasts inside fused reductions)."""
    from repro.models.shard_hooks import constrain

    if cfg.tie_embeddings:
        w = p_embed["table"].astype(x.dtype).T
        logits = x @ w
    else:
        logits = linear(p_head, x)
    logits = constrain(logits, "logits")
    if cfg.logit_softcap is not None:
        logits = _softcap(logits.astype(jnp.float32),
                          cfg.logit_softcap).astype(x.dtype)
    return logits


@jax.custom_vjp
def sharded_xent(logits, targets):
    """Cross-entropy that stays V-sharding-friendly.

    Avoids `take_along_axis` over the vocab axis (GSPMD would all-gather the
    sharded logits) by using a one-hot contraction; all (B,S,V)-sized math
    stays in the logits dtype (bf16 on TPU) with f32 upcasts only inside
    fused reductions. logits: (B,S,V); targets: (B,S) int32.
    Returns per-token nll (B,S) f32.
    """
    nll, _ = _xent_fwd(logits, targets)
    return nll


def _xent_fwd(logits, targets):
    lf = logits.astype(jnp.float32)  # fused into the reductions below
    m = jnp.max(lf, axis=-1)
    logz = jnp.log(jnp.sum(jnp.exp(lf - m[..., None]), axis=-1)) + m
    onehot = jax.nn.one_hot(targets, logits.shape[-1], dtype=jnp.float32)
    tgt = jnp.sum(lf * onehot, axis=-1)
    nll = logz - tgt
    return nll, (logits, targets, logz)


def _xent_bwd(res, g):
    logits, targets, logz = res
    probs = jnp.exp(logits.astype(jnp.float32) - logz[..., None])
    onehot = jax.nn.one_hot(targets, logits.shape[-1], dtype=jnp.float32)
    dlogits = ((probs - onehot) * g[..., None]).astype(logits.dtype)
    return dlogits, None


sharded_xent.defvjp(_xent_fwd, _xent_bwd)


def sinusoidal_positions(length: int, d: int):
    return sinusoidal_at(jnp.arange(length), d)


def sinusoidal_at(positions, d: int):
    """Sinusoidal positional encoding evaluated at `positions` (any shape).

    Computed on the fly (no (max_len, d) table — decode positions can reach
    500k+). Returns positions.shape + (d,)."""
    pos = positions[..., None].astype(jnp.float32)
    dim = jnp.arange(0, d, 2).astype(jnp.float32)
    ang = pos / jnp.power(10000.0, dim / d)
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)
