"""Mellum2: a decoder whose layers repeat (sliding, sliding, sliding,
full) attention, GQA with rope per layer kind (plain on the sliding
layers, YaRN on the full ones), and a sparse expert MLP in every layer:
a softmax router over ``num_router_experts``, the top
``num_experts_per_tok`` renormalised, SwiGLU experts of width
``moe_intermediate_size``, no shared expert.  RMSNorm before each
sub-layer, an untied output head.

The configuration holds ``num_experts`` of the router's experts (the
first ones): one chip's share of an expert-parallel deployment.  The
router keeps its full width and its top-k; only the held experts' part
of each expert layer's result is computed and passed on, here and in the
program alike.

The functions the harness reads are those of ``bench/arch/decoder.py``'s
docstring.  Departures of ``row_losses`` from the published model: rope
rotates interleaved pairs (the program's layout); no auxiliary loss and
no MTP head (config.json gives neither); the experts run as a dense loop
over the held experts, every token through each, weighted by its gate
where the expert is among its choices and by 0 elsewhere (no capacity, so
no token is dropped).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from bench import work
from bench.reference import causal_attention, rms_norm

KINDS = {"sliding_attention": "local", "full_attention": "attn"}


def _dims(conf: dict):
    d = conf["hidden_size"]
    return (d, conf["num_attention_heads"], conf["num_key_value_heads"],
            conf["head_dim"], conf["moe_intermediate_size"],
            conf["vocab_size"])


def _experts(conf: dict):
    """(router width, experts held, experts per token)."""
    return (conf["num_router_experts"], conf["num_experts"],
            conf["num_experts_per_tok"])


def _layer_types(conf: dict) -> list[str]:
    return conf["layer_types"][:conf["num_hidden_layers"]]


def _period(conf: dict) -> int:
    """Layers in one repetition of the layer kinds; the layers run must be
    whole repetitions (the program stacks one group per repetition)."""
    kinds, n = _layer_types(conf), conf["num_hidden_layers"]
    for p in range(1, n + 1):
        if n % p == 0 and all(kinds[i] == kinds[i % p] for i in range(n)):
            return p
    raise ValueError(f"{conf['name']}: {n} layers hold no whole period")


def _window(conf: dict, kind: str):
    return conf["sliding_window"] if kind == "sliding_attention" else None


# ------------------------------------------------------------------ weights


def param_shapes(conf: dict) -> dict:
    """Parameter tree of shapes, the program's ``init_lm`` layout: one
    stacked group per period, block ``b<i>`` its i-th layer, with the
    router over all experts and the held experts' weights."""
    d, h, hkv, dh, f, v = _dims(conf)
    e, held, _ = _experts(conf)
    p = _period(conf)
    g = conf["num_hidden_layers"] // p
    block = {
        "norm1": {"scale": (g, d)},
        "attn": {"wq": {"w": (g, d, h * dh)},
                 "wk": {"w": (g, d, hkv * dh)},
                 "wv": {"w": (g, d, hkv * dh)},
                 "wo": {"w": (g, h * dh, d)}},
        "norm2": {"scale": (g, d)},
        "moe": {"router": {"w": (g, d, e)},
                "w_gate": (g, held, d, f),
                "w_up": (g, held, d, f),
                "w_down": (g, held, f, d)},
    }
    return {
        "embed": {"table": (v, d)},
        "groups": {f"b{i}": block for i in range(p)},
        "final_norm": {"scale": (d,)},
        "lm_head": {"w": (d, v)},
    }


def init_leaf(path: str, key, shape):
    """RMSNorm scales 1, the embedding N(0, 1), matmul weights and the
    router N(0, 1/fan_in)."""
    if "norm" in path:
        return jnp.ones(shape, jnp.float32)
    std = 1.0 if path.startswith("embed") else 1.0 / math.sqrt(shape[-2])
    return std * jax.random.normal(key, shape, jnp.float32)


# -------------------------------------------------------------------- model


def inv_frequencies(conf: dict, kind: str):
    """(inverse frequencies (head_dim / 2,), attention factor) of a layer
    kind's rope, from ``rope_parameters``: plain, theta^(-2i/d); YaRN as
    Hugging Face's ``_compute_yarn_parameters`` computes them."""
    rp = conf["rope_parameters"][kind]
    dh, theta = conf["head_dim"], float(rp["rope_theta"])
    pos_freqs = theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    if rp["rope_type"] == "default":
        return 1.0 / pos_freqs, 1.0
    assert rp["rope_type"] == "yarn", rp
    orig = rp["original_max_position_embeddings"]

    def correction_dim(rotations):
        return dh * math.log(orig / (rotations * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(correction_dim(rp["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rp["beta_slow"])), dh - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dh // 2, dtype=jnp.float32) - low)
                    / (high - low), 0, 1)
    extrapolation = 1 - ramp
    inv = (1.0 / (rp["factor"] * pos_freqs) * (1 - extrapolation)
           + 1.0 / pos_freqs * extrapolation)
    return inv, rp.get("attention_factor",
                       0.1 * math.log(rp["factor"]) + 1.0)


def rope(x, inv_freq, factor):
    """x: (R, S, H, dh); rotates the pairs (2i, 2i+1) by position times
    ``inv_freq[i]``, cos and sin scaled by ``factor``."""
    s, dh = x.shape[1], x.shape[-1]
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq
    cos = factor * jnp.cos(ang)[:, None, :]
    sin = factor * jnp.sin(ang)[:, None, :]
    xr = x.astype(jnp.float32).reshape(x.shape[:-1] + (dh // 2, 2))
    a, b = xr[..., 0], xr[..., 1]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape).astype(x.dtype)


def held_experts(m, conf, y):
    """The held experts' part of the expert layer for y (R, S, D): softmax
    over all router logits, top-k renormalised, and for each held expert
    every token through its SwiGLU times the token's gate on it (0 where
    the expert is not among its choices)."""
    e, held, k = _experts(conf)
    probs = jax.nn.softmax((y @ m["router"]["w"]).astype(jnp.float32), -1)
    top, idx = jax.lax.top_k(probs, k)
    top = top / top.sum(-1, keepdims=True)
    out = jnp.zeros_like(y)
    for j in range(held):
        gate = jnp.sum(jnp.where(idx == j, top, 0.0), axis=-1)
        act = jax.nn.silu(y @ m["w_gate"][j]) * (y @ m["w_up"][j])
        out = out + gate[..., None].astype(y.dtype) * (act @ m["w_down"][j])
    return out


def row_losses(params, conf, tokens, targets, prefix, row_w, dtype):
    """(sum of position losses times row weights, sum of their weights);
    both add up over blocks of rows (there is no auxiliary term)."""
    d, h, hkv, dh, *_ = _dims(conf)
    eps = conf["rms_norm_eps"]
    period = _period(conf)
    p = jax.tree.map(lambda a: a.astype(dtype), params)
    r, s = tokens.shape

    # each layer recomputed in the backward pass, so that the reference's
    # step fits beside its parameters, Adam's moments and the gradients it
    # adds up on one chip
    @functools.partial(jax.checkpoint, static_argnums=(2,))
    def layer(x, lp, kind):
        inv, factor = inv_frequencies(conf, kind)
        y = rms_norm(x, lp["norm1"]["scale"], eps)
        q = (y @ lp["attn"]["wq"]["w"]).reshape(r, s, h, dh)
        k = (y @ lp["attn"]["wk"]["w"]).reshape(r, s, hkv, dh)
        v = (y @ lp["attn"]["wv"]["w"]).reshape(r, s, hkv, dh)
        o = causal_attention(rope(q, inv, factor), rope(k, inv, factor), v,
                             _window(conf, kind))
        x = x + o.reshape(r, s, h * dh) @ lp["attn"]["wo"]["w"]
        return x + held_experts(lp["moe"], conf,
                                rms_norm(x, lp["norm2"]["scale"], eps))

    x = p["embed"]["table"][tokens]
    for i, kind in enumerate(_layer_types(conf)):
        x = layer(x, jax.tree.map(lambda a: a[i // period],
                                  p["groups"][f"b{i % period}"]), kind)
    x = rms_norm(x, p["final_norm"]["scale"], eps)
    logits = (x @ p["lm_head"]["w"]).astype(jnp.float32)
    nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
        logits, targets[..., None], axis=-1)[..., 0]
    w = jnp.broadcast_to(row_w[:, None], nll.shape)
    return jnp.sum(nll * w), jnp.sum(w)


# ------------------------------------------------------------- the program


def program_config(conf: dict):
    """The program's ModelConfig for a configuration file: the layer kinds
    of one period, a window and a rope per kind, the router's width and
    the experts held.  The file's ``program`` may set ``remat`` and
    ``remat_policy``."""
    from repro.configs import get_config
    from repro.models.config import Rope

    cfg = get_config(conf["program"]["arch"])
    if cfg.family != conf["program"]["family"]:
        raise ValueError(f"{conf['name']}: the program's {cfg.name} is "
                         f"{cfg.family}, the file says "
                         f"{conf['program']['family']}")
    if not conf["norm_topk_prob"] or set(conf["mlp_layer_types"]) != {
            "sparse"}:
        raise ValueError(f"{conf['name']}: the program renormalises the "
                         f"top-k and has no dense MLP layers")
    d, h, hkv, dh, f, v = _dims(conf)
    e, held, k = _experts(conf)
    kinds = _layer_types(conf)[:_period(conf)]
    rope = {}
    for kind in set(kinds):
        rp = conf["rope_parameters"][kind]
        rope[KINDS[kind]] = Rope(float(rp["rope_theta"])) \
            if rp["rope_type"] == "default" else Rope(
                float(rp["rope_theta"]), yarn_factor=float(rp["factor"]),
                yarn_original_len=rp["original_max_position_embeddings"],
                yarn_beta_fast=float(rp["beta_fast"]),
                yarn_beta_slow=float(rp["beta_slow"]),
                yarn_attention_factor=rp.get("attention_factor"))
    extra = {k_: conf["program"][k_] for k_ in ("remat", "remat_policy")
             if k_ in conf["program"]}
    return cfg.with_(
        num_layers=conf["num_hidden_layers"], d_model=d, vocab_size=v,
        num_heads=h, num_kv_heads=hkv, head_dim=dh, moe_d_ff=f,
        num_experts=e, experts_held=held, moe_top_k=k,
        block_pattern=tuple(KINDS[x] for x in kinds),
        local_window=conf["sliding_window"], window=None,
        rope_theta=rope.get("local", rope.get("attn")).theta,
        rope_by_kind=tuple(sorted(rope.items())),
        norm_eps=conf["rms_norm_eps"],
        tie_embeddings=conf["tie_word_embeddings"], **extra)


# --------------------------------------------------------------------- work


def attention_params(conf: dict) -> int:
    """The q, k, v and output projections of one layer."""
    d, h, hkv, dh, *_ = _dims(conf)
    return 2 * d * h * dh + 2 * d * hkv * dh


def expert_params(conf: dict) -> int:
    """One expert's SwiGLU gate, up and down matrices."""
    d, _, _, _, f, _ = _dims(conf)
    return 3 * d * f


def expected_pairs(conf: dict, tokens: float) -> float:
    """(token, choice) pairs that land on held experts in one layer, at
    their expectation: each of the top-k choices falls on a held expert
    with probability held / router width."""
    e, held, k = _experts(conf)
    return tokens * k * held / e


def param_count(conf: dict) -> int:
    """Every parameter: per layer the attention projections, the router,
    the held experts and two RMSNorm scales; the embedding, the head and
    the final scale."""
    d, *_, v = _dims(conf)
    e, held, _ = _experts(conf)
    layer = attention_params(conf) + d * e + held * expert_params(conf) \
        + 2 * d
    return conf["num_hidden_layers"] * layer + 2 * v * d + d


def train_flops_per_position(conf: dict, seq: int) -> float:
    """Useful FLOPs of a forward and backward pass, per position of a
    ``seq``-long row: 6 per matmul weight a token meets (the projections,
    the router, the held experts at the expected pairs per token, the
    head) plus the attention products over each layer kind's kept pairs,
    4·dh per pair and head forward and 8·dh backward."""
    d, h, _, dh, _, v = _dims(conf)
    layers = conf["num_hidden_layers"]
    e, *_ = _experts(conf)
    per_token = layers * (attention_params(conf) + d * e
                          + expected_pairs(conf, 1) * expert_params(conf))
    attn = sum(12 * dh * h * work.attention_pairs(seq, _window(conf, kind))
               for kind in _layer_types(conf))
    return 6 * (per_token + d * v) + attn / seq


def expert_work(conf: dict, pairs: float) -> dict:
    """(FLOPs, bytes) of the grouped-matmul kernels over ``pairs`` (token,
    choice) pairs in one layer: stem ``gmm``, the forward's gate, up and
    down products and the backward's three input gradients; stem
    ``tgmm``, the backward's three weight gradients.  2·d·f FLOPs a pair
    and product; each call reads its operands and writes its result once
    (float32), the held experts' weights whole; the recomputation that
    remat and the checkpointed token groups make is not counted."""
    d, _, _, _, f, _ = _dims(conf)
    _, held, _ = _experts(conf)
    w = held * d * f                     # one projection's held weights
    flops = 3 * 2 * d * f * pairs
    # forward: gate, up read x (P, d), write (P, f); down reads (P, f),
    # writes (P, d); the input gradients mirror them
    per_pass = 3 * w + pairs * (2 * (d + f) + (f + d))
    # weight gradients: each reads its input and output gradient, writes w
    tgmm = 3 * w + pairs * 3 * (d + f)
    return {"gmm": (2 * flops, 2 * per_pass * work.F32),
            "tgmm": (flops, tgmm * work.F32)}


def kernel_work(conf: dict, seq: int, valid_rows: int) -> dict:
    """(FLOPs, bytes) of each Pallas kernel the step runs, by the stem of
    its op name, over ``valid_rows`` rows and all layers: ``attention``
    over each layer's kind and window; ``gmm`` and ``tgmm`` over the pairs
    on held experts at their expectation, valid tokens x top-k x held /
    router width a layer (the trainer's ``expert_pairs`` counts the
    pairs routed)."""
    _, h, hkv, dh, *_ = _dims(conf)
    out = {"attention": (0.0, 0.0), "gmm": (0.0, 0.0), "tgmm": (0.0, 0.0)}
    one = expert_work(conf, expected_pairs(conf, valid_rows * seq))
    for kind in _layer_types(conf):
        parts = dict(one, attention=work.flash_work(
            seq, h, hkv, dh, valid_rows, _window(conf, kind)))
        out = {k: (out[k][0] + parts[k][0], out[k][1] + parts[k][1])
               for k in out}
    return out
