"""The plain reference of a training step, and the comparison that decides
a run's ``correct``.

The reference imports nothing of the program.  It takes the weights and
rows from ``bench/inputs.py`` (made again from the seed), and follows the
configuration in straightforward ``jax.numpy``: the forward pass and loss
are the ``row_losses`` of the configuration's architecture module
(``bench/arch/<model>.py``), built from the helpers here (RMSNorm, rope
on interleaved pairs, causal softmax attention with grouped KV heads and
an optional window).  Each worker's gradient is the mean over its valid
rows' positions; the workers' gradients are combined with weights
b_k / sum(b); Adam updates the parameters.  In float32 every matmul runs
at ``highest`` precision.

It runs in blocks of rows, one row per device at a time, with attention
in blocks of queries, so that it fits beside nothing else on the chip.

The control is the same reference in bfloat16 (parameters, activations
and gradients; statistics of norms, softmax and loss in float32): the
precision below the float32 the configurations state.
"""

from __future__ import annotations

import math
import statistics

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bench.inputs import Feed, make_params

Q_BLOCK = 512


# ------------------------------------------------- helpers of the models


def rms_norm(x, scale, eps):
    xf = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * inv).astype(x.dtype) * scale.astype(x.dtype)


def rope(x, theta):
    """x: (R, S, H, dh); rotates the pairs (2i, 2i+1) by position."""
    s, dh = x.shape[1], x.shape[-1]
    half = dh // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    xr = x.astype(jnp.float32).reshape(x.shape[:-1] + (half, 2))
    a, b = xr[..., 0], xr[..., 1]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape).astype(x.dtype)


def causal_attention(q, k, v, window=None):
    """q: (R, S, H, dh), k, v: (R, S, Hkv, dh); softmax in float32, one
    block of queries at a time (recomputed in the backward pass).  With
    ``window``, query q sees key k only where q - k < window."""
    r, s, h, dh = q.shape
    rep = h // k.shape[2]
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)

    @jax.checkpoint
    def block(qb, start):
        logits = jnp.einsum("rqhd,rkhd->rhqk", qb, k,
                            preferred_element_type=jnp.float32)
        logits = logits / math.sqrt(dh)
        qpos = start + jnp.arange(qb.shape[1])
        keep = jnp.arange(s)[None, :] <= qpos[:, None]
        if window is not None:
            keep &= jnp.arange(s)[None, :] > qpos[:, None] - window
        logits = jnp.where(keep, logits, -jnp.inf)
        probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
        return jnp.einsum("rhqk,rkhd->rqhd", probs, v,
                          preferred_element_type=jnp.float32).astype(q.dtype)

    qb = min(Q_BLOCK, s)
    return jnp.concatenate([block(q[:, i:i + qb], i)
                            for i in range(0, s, qb)], axis=1)


# --------------------------------------------------------------- the steps


class Reference:
    """Training steps of the plain model on the rows a run logged."""

    def __init__(self, spec: dict, seed: int, dtype: str = "float32",
                 devices=None):
        """``spec``: a cell as ``harness.load_cell`` reads it (its
        configuration, traffic and architecture module)."""
        conf, traffic = spec["config"], spec["traffic"]
        self.arch, self.conf, self.seed = spec["arch"], conf, seed
        self.dtype = jnp.dtype(dtype)
        self.opt = traffic["optimizer"]
        self.feed = Feed(conf, traffic["seq_len"], seed)
        devices = list(devices or jax.devices())
        self.mesh = Mesh(np.array(devices), ("rows",))
        self.rows_sharding = NamedSharding(self.mesh, P("rows"))
        self.replicated = NamedSharding(self.mesh, P())
        self.block = len(devices)
        grad = jax.value_and_grad(self._loss, has_aux=True)
        self._grad = jax.jit(grad, out_shardings=self.replicated)
        self._adam = jax.jit(self._adam_update, out_shardings=self.replicated)

    def _loss(self, params, tokens, targets, prefix, row_w):
        loss, count = self.arch.row_losses(params, self.conf, tokens,
                                           targets, prefix, row_w,
                                           self.dtype)
        return loss, count

    def _precision(self):
        return jax.default_matmul_precision(
            "highest" if self.dtype == jnp.float32 else "default")

    def worker_grad(self, params, worker, call, n_valid):
        """Mean gradient over ``n_valid`` rows of one fetch, and the sums
        of losses and weights behind it."""
        g_sum = jax.tree.map(jnp.zeros_like, params)
        loss = count = 0.0
        padded = -(-n_valid // self.block) * self.block
        rows = self.feed.rows(worker, call, padded)
        rows.setdefault("prefix", None)
        row_w = (np.arange(padded) < n_valid).astype(np.float32)
        with self._precision():
            for start in range(0, n_valid, self.block):
                take = slice(start, start + self.block)
                args = [None if rows[k] is None else
                        jax.device_put(rows[k][take], self.rows_sharding)
                        for k in ("tokens", "targets", "prefix")]
                w = jax.device_put(row_w[take], self.rows_sharding)
                (l_sum, w_sum), g = self._grad(params, *args, w)
                g_sum = jax.tree.map(
                    lambda a, b: a + b.astype(jnp.float32), g_sum, g)
                loss, count = loss + l_sum, count + w_sum
        count = float(count)
        return (jax.tree.map(lambda a: a / count, g_sum), float(loss),
                count)

    def _adam_update(self, params, grads, m, v, t):
        o = self.opt
        b1, b2, eps, lr = o["b1"], o["b2"], o["eps"], o["lr"]
        m = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * g, m, grads)
        v = jax.tree.map(lambda v_, g: b2 * v_ + (1 - b2) * g * g, v, grads)
        bc1, bc2 = 1 - b1 ** t, 1 - b2 ** t
        params = jax.tree.map(
            lambda p, m_, v_: (p.astype(jnp.float32) - lr * (m_ / bc1)
                               / (jnp.sqrt(v_ / bc2) + eps)).astype(p.dtype),
            params, m, v)
        return params, m, v

    def run(self, steps) -> dict:
        """``steps``: per step, per worker ``(worker, call, n_valid)``.

        Returns the loss of each step, the leaf norms of the first step's
        combined gradient and of the parameters' change over all steps."""
        params0 = jax.device_put(
            make_params(self.arch, self.conf, self.seed), self.replicated)
        params = jax.tree.map(lambda a: a.astype(self.dtype), params0)
        m = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), params0)
        v = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), params0)
        losses, grad_norms = [], None
        for t, fetches in enumerate(steps):
            total = sum(n for _, _, n in fetches)
            g, loss, count = None, 0.0, 0.0
            for worker, call, n in fetches:
                g_k, l_k, c_k = self.worker_grad(params, worker, call, n)
                lam = n / total
                g = jax.tree.map(lambda a: lam * a, g_k) if g is None else \
                    jax.tree.map(lambda a, b: a + lam * b, g, g_k)
                loss += l_k
                count += c_k
                del g_k
            losses.append(loss / count)
            if t == 0:
                grad_norms = leaf_norms(g)
            params, m, v = self._adam(params, g, m, v, float(t + 1))
            del g
        delta = jax.tree.map(lambda a, b: a.astype(jnp.float32) - b,
                             params, params0)
        return {"losses": losses, "grad_norms": grad_norms,
                "delta_norms": leaf_norms(delta)}


def leaf_norms(tree) -> dict:
    """{leaf path: L2 norm} of a parameter tree, as Python floats."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    norms = [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
             for _, x in flat]
    return {"/".join(str(getattr(k, "key", k)) for k in path): float(n)
            for (path, _), n in zip(flat, jax.device_get(norms))}


# ------------------------------------------------------------ the comparison


def norm_gap(prog: dict, ref: dict, leaves=None) -> tuple[float, str]:
    """Worst leaf of |‖prog‖ − ‖ref‖| / max(‖ref leaf‖, median ‖ref‖):
    a leaf whose norm is all but zero is measured against the median
    leaf.  Returns the gap and the leaf that set it."""
    leaves = sorted(ref) if leaves is None else leaves
    floor = statistics.median(ref[k] for k in ref)
    worst, at = 0.0, ""
    for k in leaves:
        gap = abs(prog[k] - ref[k]) / max(ref[k], floor)
        if math.isnan(gap):           # never within a limit
            return gap, k
        if gap > worst:
            worst, at = gap, k
    return worst, at


def moving_leaves(ref_grad_norms: dict, rule: float = 1e-3) -> list[str]:
    """Leaves whose reference gradient is more than ``rule`` of the median
    leaf's: the others move under Adam by round-off alone."""
    med = statistics.median(ref_grad_norms.values())
    return sorted(k for k, n in ref_grad_norms.items() if n > rule * med)


def compare(prog: dict, ref: dict) -> dict:
    """The numbers that decide ``correct``: each step's loss gap (relative
    to the reference), the worst leaf's gap of the first gradient's norm,
    and of the norm of the parameters' change over the checked steps."""
    out = {}
    for i, (a, b) in enumerate(zip(prog["losses"], ref["losses"]), 1):
        out[f"loss_gap.{i}"] = abs(a - b) / abs(b)
    out["grad_gap"], _ = norm_gap(prog["grad_norms"], ref["grad_norms"])
    out["delta_gap"], _ = norm_gap(prog["delta_norms"], ref["delta_norms"],
                                   moving_leaves(ref["grad_norms"]))
    return out
