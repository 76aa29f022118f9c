"""The dense decoder: GQA (or MHA) causal self-attention with rope and a
SwiGLU MLP in every layer, RMSNorm before each, an untied output head.
Phi-3-vision's language backbone (with an image prefix of
``num_image_tokens`` positions) and Yi-9B are this architecture.

A configuration file names its architecture by ``"model"``; the harness
loads ``bench/arch/<model>.py`` by path and reads these functions from it:

- ``param_shapes(conf)`` and ``init_leaf(path, key, shape)``: the weights
  a run and its reference make from the seed (``bench/inputs.py``);
- ``row_losses(...)``: the plain forward pass and loss the reference
  differentiates (``bench/reference.py``);
- ``program_config(conf)``: the program's ``ModelConfig`` for the file;
- ``param_count(conf)``, ``train_flops_per_position(conf, seq)`` and
  ``kernel_work(conf, seq, valid_rows)``: the work the metrics divide by,
  counted by the rules of ``bench/work.py``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench import work
from bench.reference import causal_attention, rms_norm, rope


def _dims(conf: dict):
    d = conf["hidden_size"]
    h = conf["num_attention_heads"]
    hkv = conf["num_key_value_heads"]
    dh = conf.get("head_dim") or d // h
    return d, h, hkv, dh, conf["intermediate_size"], conf["vocab_size"]


# ------------------------------------------------------------------ weights


def param_shapes(conf: dict) -> dict:
    """Parameter tree of shapes: embedding, stacked layers, final norm and
    output head (the program's ``init_lm`` layout)."""
    d, h, hkv, dh, ff, v = _dims(conf)
    n = conf["num_hidden_layers"]
    return {
        "embed": {"table": (v, d)},
        "groups": {"b0": {
            "norm1": {"scale": (n, d)},
            "attn": {"wq": {"w": (n, d, h * dh)},
                     "wk": {"w": (n, d, hkv * dh)},
                     "wv": {"w": (n, d, hkv * dh)},
                     "wo": {"w": (n, h * dh, d)}},
            "norm2": {"scale": (n, d)},
            "mlp": {"w_gate": {"w": (n, d, ff)},
                    "w_up": {"w": (n, d, ff)},
                    "w_down": {"w": (n, ff, d)}},
        }},
        "final_norm": {"scale": (d,)},
        "lm_head": {"w": (d, v)},
    }


def init_leaf(path: str, key, shape):
    """RMSNorm scales 1, the embedding N(0, 1), matmul weights
    N(0, 1/fan_in)."""
    if "norm" in path:
        return jnp.ones(shape, jnp.float32)
    std = 1.0 if path.startswith("embed") else 1.0 / math.sqrt(shape[-2])
    return std * jax.random.normal(key, shape, jnp.float32)


# -------------------------------------------------------------------- model


def row_losses(params, conf, tokens, targets, prefix, row_w, dtype):
    """(sum of position losses times row weights, sum of their weights).

    Both are sums over the rows given, so that the reference's blocks of
    rows add up to the whole fetch: a term an architecture adds to the
    loss (an expert layer's auxiliary loss) has to be such a sum too."""
    d, h, hkv, dh, *_ = _dims(conf)
    eps, theta = conf["rms_norm_eps"], conf["rope_theta"]
    p = jax.tree.map(lambda a: a.astype(dtype), params)
    r, s = tokens.shape
    x = p["embed"]["table"][tokens]
    n_prefix = 0 if prefix is None else prefix.shape[1]
    if n_prefix:
        x = jnp.concatenate([prefix.astype(dtype), x[:, n_prefix:]], axis=1)
    layers = p["groups"]["b0"]
    for i in range(conf["num_hidden_layers"]):
        lp = jax.tree.map(lambda a: a[i], layers)
        y = rms_norm(x, lp["norm1"]["scale"], eps)
        q = (y @ lp["attn"]["wq"]["w"]).reshape(r, s, h, dh)
        k = (y @ lp["attn"]["wk"]["w"]).reshape(r, s, hkv, dh)
        v = (y @ lp["attn"]["wv"]["w"]).reshape(r, s, hkv, dh)
        o = causal_attention(rope(q, theta), rope(k, theta), v)
        x = x + o.reshape(r, s, h * dh) @ lp["attn"]["wo"]["w"]
        y = rms_norm(x, lp["norm2"]["scale"], eps)
        m = lp["mlp"]
        x = x + (jax.nn.silu(y @ m["w_gate"]["w"]) * (y @ m["w_up"]["w"])
                 ) @ m["w_down"]["w"]
    x = rms_norm(x, p["final_norm"]["scale"], eps)
    logits = (x @ p["lm_head"]["w"]).astype(jnp.float32)
    nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
        logits, targets[..., None], axis=-1)[..., 0]
    pos_w = (jnp.arange(s) >= n_prefix).astype(jnp.float32)
    w = row_w[:, None] * pos_w[None, :]
    return jnp.sum(nll * w), jnp.sum(w)


# ------------------------------------------------------------- the program


def program_config(conf: dict):
    """The program's ModelConfig for a configuration file."""
    from repro.configs import get_config

    cfg = get_config(conf["program"]["arch"])
    if cfg.family != conf["program"]["family"]:
        raise ValueError(f"{conf['name']}: the program's {cfg.name} is "
                         f"{cfg.family}, the file says "
                         f"{conf['program']['family']}")
    d, h, hkv, dh, ff, v = _dims(conf)
    return cfg.with_(
        num_layers=conf["num_hidden_layers"], d_model=d, vocab_size=v,
        num_heads=h, num_kv_heads=hkv, head_dim=dh, d_ff=ff,
        rope_theta=conf["rope_theta"], norm_eps=conf["rms_norm_eps"],
        num_patches=int(conf.get("num_image_tokens", 0)),
        tie_embeddings=conf["tie_word_embeddings"])


# --------------------------------------------------------------------- work


def layer_matmul_params(conf: dict) -> int:
    """Weights of one layer that multiply activations: the q, k, v and
    output projections and the SwiGLU gate, up and down matrices."""
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


def train_flops_per_position(conf: dict, seq: int) -> float:
    """Useful FLOPs of a forward and backward pass, per position of a
    ``seq``-long row: 6 per matmul weight (2 forward, 4 backward) plus the
    two attention products (QK^T and PV) over the causal pairs, three
    times over for the backward."""
    d, h, hkv, dh, *_ = _dims(conf)
    attn = (conf["num_hidden_layers"] * 3 * 4 * dh * h
            * work.attention_pairs(seq))
    return 6 * matmul_params(conf) + attn / seq


def kernel_work(conf: dict, seq: int, valid_rows: int) -> dict:
    """(FLOPs, bytes) of each Pallas kernel the step runs, by the stem of
    its op name, over ``valid_rows`` rows and all layers."""
    d, h, hkv, dh, *_ = _dims(conf)
    rows = valid_rows * conf["num_hidden_layers"]
    return {"attention": work.flash_work(seq, h, hkv, dh, rows)}
