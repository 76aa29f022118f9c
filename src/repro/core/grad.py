"""Weighted gradient combination (paper Eq. 2-3), JAX-native.

    g_k   = lambda_k * grad_k,  lambda_k = b_k / sum_i b_i
    x_t+1 = x_t - eta * sum_k g_k

Two implementations:
  * `combine_weighted` — host/driver-side combine over a list of worker
    gradient pytrees (multislice mode; the all-reduce is jnp arithmetic here,
    on real hardware it is a cross-slice psum with the same weights).
  * `weighted_psum` — in-graph combine over a mesh axis (spmd/dry-run mode):
    each shard contributes its local sum of example-gradients; dividing by
    the global *weight* sum (not the device count) realizes the weighted
    average in one all-reduce.

Because the division is by the MASK-WEIGHT sum, padded rows (mask 0) drop
out of both numerator and denominator — which is exactly what lets the mesh
execution backend (`repro.train.mesh`, DESIGN.md §11) pad ragged per-worker
batches up to bucketed shapes without perturbing the gradient: the padded
result equals the unpadded `combine_weighted` combine bit-for-bit in exact
arithmetic (allclose under fp32).
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp


def tree_sqnorm(tree):
    """Squared L2 norm of a gradient pytree, |g|^2 = sum over leaves of sum(x^2).

    Accumulated in fp32 regardless of leaf dtype.  This is the side statistic
    the gradient-noise-scale estimator (DESIGN.md §15) needs from each
    worker's mean gradient and from the combined gradient; it is meant to be
    evaluated INSIDE the already-jitted accumulation/psum call so estimation
    costs no extra pass over the model.
    """
    leaves = jax.tree_util.tree_leaves(tree)
    if not leaves:
        return jnp.zeros((), jnp.float32)
    out = jnp.zeros((), jnp.float32)
    for leaf in leaves:
        out = out + jnp.sum(jnp.square(leaf.astype(jnp.float32)))
    return out


def combine_weighted(grads: Sequence, batches: Sequence[int]):
    """Weighted average of per-worker gradient pytrees with lambda_k weights."""
    if len(grads) != len(batches):
        raise ValueError("one gradient pytree per worker required")
    total = float(sum(batches))
    if total <= 0:
        raise ValueError("global batch must be positive")
    lams = [b / total for b in batches]

    def _wsum(*leaves):
        out = lams[0] * leaves[0]
        for lam, leaf in zip(lams[1:], leaves[1:]):
            out = out + lam * leaf
        return out

    return jax.tree_util.tree_map(_wsum, *grads)


def combine_weighted_with_sqnorm(grads: Sequence, batches: Sequence[int]):
    """`combine_weighted` plus the combined gradient's squared norm.

    Returns ``(g, |g|^2)`` where g is the lambda-weighted combine.  Together
    with the per-worker |g_k|^2 side stats carried out of each worker's
    jitted call, this is the large-batch half of the small-batch/large-batch
    critical-batch estimator (DESIGN.md §15) — no extra gradient pass.
    """
    g = combine_weighted(grads, batches)
    return g, tree_sqnorm(g)


def weighted_psum(local_grad_sum, local_weight_sum, axis_names):
    """In-graph weighted mean across mesh axes.

    Args:
      local_grad_sum: pytree of sum_{examples in shard} w_i * grad_i.
      local_weight_sum: scalar sum of example weights in this shard.
      axis_names: mesh axis name or tuple of names to reduce over.

    Returns the globally weighted-average gradient pytree: this is exactly
    Eq. 3 with lambda weighting when w_i encode the variable-batch masks.
    """
    gsum = jax.tree_util.tree_map(
        lambda g: jax.lax.psum(g, axis_names), local_grad_sum
    )
    wsum = jax.lax.psum(local_weight_sum, axis_names)
    return jax.tree_util.tree_map(lambda g: g / jnp.maximum(wsum, 1e-8), gsum)


def weighted_psum_with_sqnorm(local_grad_sum, local_weight_sum, axis_names):
    """`weighted_psum` plus the squared norm of this worker's mean gradient.

    The sqnorm is of the LOCAL (per-worker-slice) weighted-mean gradient —
    i.e. |g_k|^2 where g_k is what this worker contributes before the
    cross-worker combine — evaluated in-graph inside the shard_mapped worker
    call (DESIGN.md §11) so the GNS estimator's per-worker moments ride the
    existing all-reduce without an extra pass.
    """
    gsum = jax.tree_util.tree_map(
        lambda g: jax.lax.psum(g, axis_names), local_grad_sum
    )
    wsum = jax.lax.psum(local_weight_sum, axis_names)
    g = jax.tree_util.tree_map(lambda g: g / jnp.maximum(wsum, 1e-8), gsum)
    return g, tree_sqnorm(g)


def accumulate_microbatch_grads(grad_fn, params, microbatches, masks):
    """Dynamic-trip-count gradient accumulation over (n_steps, m, ...) data.

    THE scan-accumulation implementation — the multislice trainer's hot path
    and the SPMD accum train step both call it, so the carry/denominator
    contract lives in exactly one place.

    `grad_fn(params, batch, mask) -> ((loss_sum, w_sum, aux), grads)` with
    grads of the weighted SUM loss (Eq. 2-3 contract); `microbatches` is a
    pytree whose leaves have leading dims (n_steps, m); `masks` is
    (n_steps, m).  Returns device-resident SUMS
    ``(grad_sums, loss_sum, weight_sum, aux_weighted_sum)`` — the caller
    divides by the weight sum once.  Uses lax.scan so the compiled program
    depends on n_steps only through the stacked data shape — the multislice
    runtime re-slices the data per plan (cheap host-side reshape).
    """

    def body(carry, xs):
        g_acc, l_acc, w_acc, a_acc = carry
        batch, mask = xs
        (loss_sum, w_sum, aux), grads = grad_fn(params, batch, mask)
        if isinstance(aux, dict):        # a model's counters beside it
            aux = aux["aux_loss"]
        g_acc = jax.tree_util.tree_map(jnp.add, g_acc, grads)
        return (g_acc, l_acc + loss_sum, w_acc + w_sum,
                a_acc + aux * w_sum), None

    zeros = jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, p.dtype), params)
    z = jnp.zeros((), jnp.float32)
    (gsum, lsum, wsum, asum), _ = jax.lax.scan(
        body, (zeros, z, z, z), (microbatches, masks)
    )
    return gsum, lsum, wsum, asum
