"""Decoder-only LM covering dense / MoE / SSM / hybrid / VLM families.

Layers are *scanned*: parameters of repeated blocks are stacked along a
leading layer axis and iterated with `jax.lax.scan`, keeping the HLO small
and compile times flat in depth — essential for the 512-device dry-runs.

Hybrid models (recurrentgemma) scan over *groups* (one period of the block
pattern, e.g. rec-rec-attn); a remainder tail shorter than one period is
applied unrolled.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models import layers as L
from repro.models import recurrent as R
from repro.models import ssm as S
from repro.models.shard_hooks import constrain


# --------------------------------------------------------------- structure


def block_pattern(cfg: ModelConfig) -> tuple[str, ...]:
    """Kinds of the repeating block group ('attn' | 'local' | 'rec' | 'ssd');
    each attention kind has its own window and rope (``cfg.window_for``,
    ``cfg.rope_for``)."""
    if cfg.family == "ssm":
        return ("ssd",)
    if cfg.family in ("hybrid", "moe"):
        return cfg.block_pattern
    return ("attn",)


def layer_counts(cfg: ModelConfig) -> tuple[int, int]:
    """(num_scanned_groups, num_tail_layers)."""
    period = len(block_pattern(cfg))
    return cfg.num_layers // period, cfg.num_layers % period


# ------------------------------------------------------------------- blocks


def init_block(key, cfg: ModelConfig, kind: str):
    ks = jax.random.split(key, 4)
    p = {"norm1": L.init_norm(cfg)}
    if kind in ("attn", "local"):
        if cfg.attention == "mla":
            p["attn"] = L.init_mla(ks[0], cfg)
        else:
            p["attn"] = L.init_gqa(ks[0], cfg)
    elif kind == "rec":
        p["rec"] = R.init_recurrent_block(ks[0], cfg)
    elif kind == "ssd":
        p["ssd"] = S.init_ssd(ks[0], cfg)
    else:
        raise ValueError(f"unknown block kind {kind}")
    if kind != "ssd":  # mamba2 blocks have no separate MLP
        p["norm2"] = L.init_norm(cfg)
        if cfg.num_experts and cfg.mlp == "moe":
            p["moe"] = L.init_moe(ks[1], cfg)
        else:
            p["mlp"] = L.init_mlp(ks[1], cfg)
    return p


def apply_block(p, x, cfg: ModelConfig, kind: str, cache, positions,
                num_valid=None):
    """One block.  Returns (x, new_cache, aux, counters): the expert
    layer's balance loss (0 without one) and what the held-expert layer
    counts (``L.apply_held_moe``; {} otherwise)."""
    h = L.apply_norm(p["norm1"], x, cfg)
    if kind in ("attn", "local"):
        window = cfg.window_for(kind)
        if cfg.attention == "mla":
            # the ragged kernel path is GQA-family only; MLA keeps the
            # loss-mask semantics for padded rows
            out, new_cache = L.mla_attention(
                p["attn"], h, cfg, positions=positions, cache=cache, window=window)
        else:
            out, new_cache = L.gqa_attention(
                p["attn"], h, cfg, positions=positions, cache=cache,
                window=window, softcap=cfg.attn_softcap, num_valid=num_valid,
                rope_spec=cfg.rope_for(kind))
    elif kind == "rec":
        out, new_cache = R.recurrent_block(p["rec"], h, cfg, cache)
    else:  # ssd
        out, new_cache = S.ssd_block(p["ssd"], h, cfg, cache)
    x = x + out
    aux = jnp.zeros((), jnp.float32)
    counters = {}
    if "moe" in p:
        h = L.apply_norm(p["norm2"], x, cfg)
        if cfg.experts_held is None:
            y, aux = L.apply_moe(p["moe"], h, cfg)
        else:
            y, aux, counters = L.apply_held_moe(p["moe"], h, cfg, num_valid)
        x = x + y
    elif "mlp" in p:
        x = x + L.apply_mlp(p["mlp"], L.apply_norm(p["norm2"], x, cfg), cfg)
    return x, new_cache, aux, counters


def add_counters(a: dict, b: dict) -> dict:
    """Counters of two blocks together: sums, and the larger of entries
    ending in ``_max``."""
    out = dict(a)
    for k, v in b.items():
        out[k] = v if k not in out else (
            jnp.maximum if k.endswith("_max") else jnp.add)(out[k], v)
    return out


def init_block_cache(cfg: ModelConfig, kind: str, batch: int, length: int, dtype):
    if kind in ("attn", "local"):
        eff = min(length, cfg.local_window) if kind == "local" else (
            min(length, cfg.window) if cfg.window else length)
        if cfg.attention == "mla":
            return L.init_mla_cache(cfg, batch, eff, dtype)
        return L.init_attn_cache(cfg, batch, eff, dtype)
    if kind == "rec":
        return R.init_recurrent_cache(cfg, batch, dtype)
    return S.init_ssd_cache(cfg, batch, dtype)


# --------------------------------------------------------------------- LM


def init_lm(key, cfg: ModelConfig):
    cfg.validate()
    pattern = block_pattern(cfg)
    n_groups, n_tail = layer_counts(cfg)
    ks = jax.random.split(key, 4 + n_tail)

    def init_group(k):
        gks = jax.random.split(k, len(pattern))
        return {f"b{i}": init_block(gk, cfg, kind)
                for i, (gk, kind) in enumerate(zip(gks, pattern))}

    params = {
        "embed": L.init_embedding(ks[0], cfg),
        "groups": jax.vmap(init_group)(jax.random.split(ks[1], n_groups)),
        "final_norm": L.init_norm(cfg),
    }
    if n_tail:
        params["tail"] = {
            f"t{i}": init_block(ks[4 + i], cfg, pattern[i]) for i in range(n_tail)
        }
    if not cfg.tie_embeddings:
        params["lm_head"] = L.init_linear(ks[2], cfg.d_model, cfg.vocab_size,
                                          cfg, use_bias=False)
    return params


def init_caches(cfg: ModelConfig, batch: int, length: int, dtype=None):
    """Stacked (groups) + unrolled (tail) cache pytree for decode."""
    dtype = dtype or cfg.act_dtype
    pattern = block_pattern(cfg)
    n_groups, n_tail = layer_counts(cfg)

    one = {f"b{i}": init_block_cache(cfg, kind, batch, length, dtype)
           for i, kind in enumerate(pattern)}
    caches = {"groups": jax.tree_util.tree_map(
        lambda leaf: jnp.zeros((n_groups,) + leaf.shape, leaf.dtype), one)}
    if n_tail:
        caches["tail"] = {
            f"t{i}": init_block_cache(cfg, pattern[i], batch, length, dtype)
            for i in range(n_tail)
        }
    return caches


def apply_lm(
    params,
    cfg: ModelConfig,
    tokens,
    *,
    prefix_embeds=None,
    caches=None,
    positions=None,
    num_valid=None,
    counters: bool = False,
):
    """Forward pass.

    tokens: (B, S) int32. prefix_embeds: optional (B, P, D) patch/frame
    embeddings overwriting the first P positions (VLM stub frontend).
    caches: decode-mode cache pytree from init_caches (S must be 1).
    num_valid: optional traced int32 valid-row count for bucket-padded
    batches, threaded to the attention kernels (DESIGN.md §14).
    Returns (logits (B,S,V) float32, new_caches, aux_loss scalar), and
    with ``counters`` the blocks' counters (``apply_block``) last.
    """
    pattern = block_pattern(cfg)
    n_groups, n_tail = layer_counts(cfg)
    b, s = tokens.shape

    x = L.embed(params["embed"], tokens, cfg)
    if prefix_embeds is not None:
        p = prefix_embeds.shape[1]
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x[:, p:]], axis=1)
    x = constrain(x, "activations")
    if positions is None:
        positions = jnp.arange(s)[None, :]

    policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
              if cfg.remat_policy == "dots" else None)

    def block(kind):
        def run(p, xc, cache):
            return apply_block(p, xc, cfg, kind, cache, positions, num_valid)
        # remat: each block is recomputed in the backward from its input,
        # so that a model of one group (one period) rematerialises too
        return jax.checkpoint(run, policy=policy) if cfg.remat else run

    def group_body(carry, xs):
        xc, aux = carry
        p_group, cache_group = xs if caches is not None else (xs, None)
        new_caches = {} if caches is not None else None
        cnt = {}
        for i, kind in enumerate(pattern):
            cache_i = None if cache_group is None else cache_group[f"b{i}"]
            xc, nc, a, c = block(kind)(p_group[f"b{i}"], xc, cache_i)
            xc = constrain(xc, "activations")
            if new_caches is not None:
                new_caches[f"b{i}"] = nc
            aux = aux + a
            cnt = add_counters(cnt, c)
        return (xc, aux), (new_caches, cnt)

    xs = params["groups"] if caches is None else (params["groups"],
                                                  caches["groups"])
    (x, aux), (new_group_caches, per_group) = jax.lax.scan(
        group_body, (x, jnp.zeros((), jnp.float32)), xs,
        unroll=cfg.scan_unroll)
    # the groups' counters, stacked by the scan, folded as blocks add them
    cnt = {k: (jnp.max if k.endswith("_max") else jnp.sum)(v, axis=0)
           for k, v in per_group.items()}

    new_caches = None
    if caches is not None:
        new_caches = {"groups": new_group_caches}
    if n_tail:
        new_tail = {}
        for i in range(n_tail):
            cache_i = caches["tail"][f"t{i}"] if caches is not None else None
            x, nc, a, c = apply_block(params["tail"][f"t{i}"], x, cfg,
                                      pattern[i], cache_i, positions,
                                      num_valid)
            new_tail[f"t{i}"] = nc
            aux = aux + a
            cnt = add_counters(cnt, c)
        if caches is not None:
            new_caches["tail"] = new_tail

    x = L.apply_norm(params["final_norm"], x, cfg)
    logits = L.unembed(params["embed"], params.get("lm_head"), x, cfg)
    if counters:
        return logits, new_caches, aux, cnt
    return logits, new_caches, aux


# ------------------------------------------------------------------- losses


def lm_loss(params, cfg: ModelConfig, tokens, targets, mask,
            prefix_embeds=None, num_valid=None, counters: bool = False):
    """Per-example-weighted cross-entropy.

    mask: (B,) example weights (the variable-batching lambda masks) or
    (B, S) token weights. num_valid: optional traced valid-row count for
    bucket-padded batches — must agree with mask (rows >= num_valid carry
    zero weight; see train/mesh.py's suffix-padding contract).
    Returns (weighted loss sum, weight sum, aux), and with ``counters``
    the blocks' counters (``apply_block``) last.
    """
    logits, _, aux, cnt = apply_lm(params, cfg, tokens,
                                   prefix_embeds=prefix_embeds,
                                   num_valid=num_valid, counters=True)
    nll = L.sharded_xent(logits, targets)
    if mask.ndim == 1:
        tok_w = jnp.broadcast_to(mask[:, None], nll.shape)
    else:
        tok_w = mask
    if prefix_embeds is not None:  # don't train on patch positions
        p = prefix_embeds.shape[1]
        tok_w = tok_w.at[:, :p].set(0.0) if hasattr(tok_w, "at") else tok_w
    loss_sum = (nll * tok_w).sum()
    w_sum = tok_w.sum()
    if counters:
        return loss_sum, w_sum, aux, cnt
    return loss_sum, w_sum, aux
