"""Gradient-combine equivalence (paper Eq. 1-3).

The central claim that makes variable batching statistically sound: the
lambda-weighted average of per-worker mean gradients over batches {b_k}
equals the plain mean gradient over the union of all examples.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import combine_weighted, example_weight_vector, weighted_psum


def _per_example_grads(params, x, y):
    def loss(p, xi, yi):
        return 0.5 * (xi @ p - yi) ** 2

    return jax.vmap(jax.grad(loss), in_axes=(None, 0, 0))(params, x, y)


def test_weighted_combine_equals_pooled_mean():
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(size=8))
    batches = [3, 5, 12]
    xs = [jnp.asarray(rng.normal(size=(b, 8))) for b in batches]
    ys = [jnp.asarray(rng.normal(size=(b,))) for b in batches]

    def mean_grad(x, y):
        g = _per_example_grads(w, x, y)
        return jax.tree_util.tree_map(lambda a: a.mean(0), g)

    per_worker = [mean_grad(x, y) for x, y in zip(xs, ys)]
    combined = combine_weighted(per_worker, batches)

    pooled = mean_grad(jnp.concatenate(xs), jnp.concatenate(ys))
    np.testing.assert_allclose(np.asarray(combined), np.asarray(pooled),
                               rtol=1e-6)


def test_combine_weighted_validates():
    g = [jnp.zeros(3)] * 2
    with pytest.raises(ValueError):
        combine_weighted(g, [1])
    with pytest.raises(ValueError):
        combine_weighted(g, [0, 0])


def test_weighted_psum_equals_masked_mean():
    """spmd-mode combine: weighted psum over a 1-axis mesh shard_map."""
    from jax.sharding import Mesh, PartitionSpec as P

    devs = np.array(jax.devices()[:1])
    mesh = Mesh(devs, ("data",))
    rng = np.random.default_rng(1)
    grads = jnp.asarray(rng.normal(size=(4, 6)))   # per-example grad sums
    weights = jnp.asarray([1.0, 1.0, 0.0, 1.0])    # one masked example

    def f(g, w):
        local = (g * w[:, None]).sum(0)
        return weighted_psum(local, w.sum(), "data")

    out = jax.shard_map(f, mesh=mesh, in_specs=(P("data"), P("data")),
                        out_specs=P())(grads, weights)
    expect = (np.asarray(grads) * np.asarray(weights)[:, None]).sum(0) / 3.0
    np.testing.assert_allclose(np.asarray(out), expect, rtol=1e-6)


def test_example_weights_reproduce_lambda_weighting():
    """spmd-mode per-example weights == Eq. 2-3 lambda weighting."""
    rng = np.random.default_rng(2)
    w = jnp.asarray(rng.normal(size=5))
    cap = 8
    batches = [2, 7]
    x = jnp.asarray(rng.normal(size=(len(batches) * cap, 5)))
    y = jnp.asarray(rng.normal(size=(len(batches) * cap,)))
    ew = jnp.asarray(example_weight_vector(batches, cap))

    def weighted_loss(p):
        per = 0.5 * (x @ p - y) ** 2
        return (per * ew).sum() / ew.sum()

    g_spmd = jax.grad(weighted_loss)(w)

    # multislice-mode equivalent
    per_worker = []
    for k, b in enumerate(batches):
        sl = slice(k * cap, k * cap + b)
        g = _per_example_grads(w, x[sl], y[sl])
        per_worker.append(jax.tree_util.tree_map(lambda a: a.mean(0), g))
    g_multi = combine_weighted(per_worker, batches)
    np.testing.assert_allclose(np.asarray(g_spmd), np.asarray(g_multi),
                               rtol=1e-6)
