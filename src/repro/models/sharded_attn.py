"""Explicitly-sharded decode attention (shard_map).

GSPMD's cost model reshards a dh-sharded KV cache to a heads-sharded layout
for the decode attention einsum — a full-cache all-gather per step that
dominates the §Roofline collective term for every big decode shape (§Perf
iteration D2, measurements v1-v4). This module removes GSPMD's freedom: the
cache update (dynamic_update_slice) and both attention contractions run
inside a shard_map over (data: batch, model: head_dim), so the only
collective is a psum of the (B, H, 1, T) logits over `model` —
~50 MB/layer instead of ~4.3 GB/layer.

Activated via shard_hooks rule "decode_attn" = (mesh, dp_axes, tp_axis),
set by the launch layer for decode programs; without it models fall back to
the plain path (CPU tests never see shard_map).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def normalize(mesh_info, batch: int):
    """Drop the dp axes when the batch doesn't divide them (e.g. batch 1
    long-context decode — the cache is data-replicated there)."""
    if mesh_info is None:
        return None
    mesh, dp_axes, tp_axis = mesh_info
    dp = int(math.prod([mesh.shape[a] for a in dp_axes])) if dp_axes else 1
    if batch % dp != 0:
        return (mesh, (), tp_axis)
    return mesh_info


def applicable(cfg, batch: int, dh: int, mesh_info) -> bool:
    if mesh_info is None:
        return False
    mesh, dp_axes, tp_axis = normalize(mesh_info, batch)
    tp = mesh.shape[tp_axis]
    return dh % tp == 0 and (dh // tp) % 2 == 0


def mla_applicable(cfg, batch: int, mesh_info) -> bool:
    if mesh_info is None:
        return False
    mesh, dp_axes, tp_axis = normalize(mesh_info, batch)
    tp = mesh.shape[tp_axis]
    return (cfg.kv_lora_rank % tp == 0
            and cfg.qk_rope_dim % tp == 0 and (cfg.qk_rope_dim // tp) % 2 == 0)


def mla_decode_attention(q_eff, q_rope, c_new, kr_new, cache_c, cache_kr,
                         idx, *, mesh_info, sm_scale: float):
    """Absorbed-MLA decode attention in latent space, cache never resharded.

    q_eff: (B,1,H,R) latent-space queries (q_nope @ W_uk);
    q_rope: (B,1,H,Dr); c_new: (B,1,R); kr_new: (B,1,1,Dr);
    cache_c: (B,T,R); cache_kr: (B,T,1,Dr).
    Returns (out_lat (B,1,H,R), probs-free), new caches. The latent rank R
    and rope dim are sharded over `model`; logits partial-sums psum once.
    """
    mesh, dp_axes, tp_axis = normalize(mesh_info, q_eff.shape[0])
    b, s, h, r = q_eff.shape

    def body(qe_b, qr_b, cn_b, krn_b, cc_b, ckr_b, idx_b):
        t = cc_b.shape[1]
        cc = jax.vmap(lambda c, u, i: jax.lax.dynamic_update_slice(
            c, u.astype(c.dtype), (i % t, 0)))(cc_b, cn_b, idx_b)
        ckr = jax.vmap(lambda c, u, i: jax.lax.dynamic_update_slice(
            c, u.astype(c.dtype), (i % t, 0, 0)))(ckr_b, krn_b, idx_b)
        logits = (jnp.einsum("bshr,btr->bhst", qe_b, cc,
                             preferred_element_type=jnp.float32)
                  + jnp.einsum("bshd,btd->bhst", qr_b, ckr[:, :, 0],
                               preferred_element_type=jnp.float32))
        logits = jax.lax.psum(logits, tp_axis) * sm_scale
        n_written = jnp.minimum(idx_b + 1, t)                  # (bb,)
        valid = jnp.arange(t)[None, :] < n_written[:, None]
        logits = jnp.where(valid[:, None, None, :], logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1)
        out_lat = jnp.einsum("bhst,btr->bshr", probs.astype(cc.dtype), cc,
                             preferred_element_type=jnp.float32)
        return out_lat.astype(qe_b.dtype), cc, ckr

    dp = tuple(dp_axes) if dp_axes else None
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(dp, None, None, tp_axis), P(dp, None, None, tp_axis),
                  P(dp, None, tp_axis), P(dp, None, None, tp_axis),
                  P(dp, None, tp_axis), P(dp, None, None, tp_axis), P(dp)),
        out_specs=(P(dp, None, None, tp_axis), P(dp, None, tp_axis),
                   P(dp, None, None, tp_axis)),
        check_vma=False,
    )(q_eff, q_rope, c_new, kr_new, cache_c, cache_kr, idx)


def decode_attention(q, k_new, v_new, cache_k, cache_v, idx, *, mesh_info,
                     softcap=None):
    """q: (B,1,H,Dh); k_new/v_new: (B,1,Hkv,Dh); caches: (B,T,Hkv,Dh).

    Returns (out (B,1,H,Dh), new_cache_k, new_cache_v). The caches keep
    their (batch@data, head_dim@model) sharding throughout."""
    mesh, dp_axes, tp_axis = normalize(mesh_info, q.shape[0])
    b, s, h, dh = q.shape
    hkv = cache_k.shape[2]
    rep = h // hkv
    sm_scale = 1.0 / math.sqrt(dh)

    def body(q_b, kn_b, vn_b, ck_b, cv_b, idx_b):
        t = ck_b.shape[1]
        ck = jax.vmap(lambda c, u, i: jax.lax.dynamic_update_slice(
            c, u.astype(c.dtype), (i % t, 0, 0)))(ck_b, kn_b, idx_b)
        cv = jax.vmap(lambda c, u, i: jax.lax.dynamic_update_slice(
            c, u.astype(c.dtype), (i % t, 0, 0)))(cv_b, vn_b, idx_b)
        bb = q_b.shape[0]
        qg = q_b.reshape(bb, s, hkv, rep, q_b.shape[-1])
        logits = jnp.einsum("bsgrd,btgd->bgrst", qg, ck,
                            preferred_element_type=jnp.float32)
        logits = jax.lax.psum(logits, tp_axis) * sm_scale
        if softcap is not None:
            logits = softcap * jnp.tanh(logits / softcap)
        n_written = jnp.minimum(idx_b + 1, t)                  # (bb,)
        valid = jnp.arange(t)[None, :] < n_written[:, None]    # (bb, t)
        logits = jnp.where(valid[:, None, None, None, :], logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bgrst,btgd->bsgrd", probs.astype(cv.dtype), cv,
                         preferred_element_type=jnp.float32)
        return out.reshape(bb, s, h, -1).astype(q_b.dtype), ck, cv

    dp = tuple(dp_axes) if dp_axes else None
    qspec = P(dp, None, None, tp_axis)
    cspec = P(dp, None, None, tp_axis)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(qspec, cspec, cspec, cspec, cspec, P(dp)),
        out_specs=(qspec, cspec, cspec),
        check_vma=False,
    )(q, k_new, v_new, cache_k, cache_v, idx)
