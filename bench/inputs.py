"""What a run feeds the system under test, made from ``--seed``: the
weights and the rows.  The reference makes them again with the same code,
so it takes nothing that the program made.

Weights are made on the device in one jitted call, in float32, as the
tree of shapes the configuration's architecture module gives
(``bench/arch/<model>.py``), each leaf by that module's ``init_leaf``.
Rows are indexed by (worker, call, row): row r of a worker's i-th fetch
is the same whatever batch size the fetch asked for, so a resized or
padded batch changes only how many rows are read.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np


def base_key(seed: int):
    """A PRNG key for any whole number: the low 31 bits and the rest."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


# ------------------------------------------------------------------ weights


def _is_shape(x) -> bool:
    return isinstance(x, tuple)


def _leaf_paths(shapes: dict) -> list[str]:
    flat, _ = jax.tree_util.tree_flatten_with_path(shapes, is_leaf=_is_shape)
    return ["/".join(str(getattr(k, "key", k)) for k in path)
            for path, _ in flat]


@functools.lru_cache(maxsize=None)
def _params_fn(arch, conf_json: str):
    shapes = arch.param_shapes(json.loads(conf_json))
    leaves, tree = jax.tree.flatten(shapes, is_leaf=_is_shape)
    paths = _leaf_paths(shapes)

    def make(key):
        return jax.tree.unflatten(tree, [
            arch.init_leaf(path, jax.random.fold_in(key, i), shape)
            for i, (path, shape) in enumerate(zip(paths, leaves))])

    return jax.jit(make)


def make_params(arch, conf: dict, seed: int):
    """The weights of a run, on the default device, in one jitted call:
    ``arch.init_leaf`` for each leaf of ``arch.param_shapes(conf)``, keyed
    by the leaf's place in the tree."""
    return _params_fn(arch, json.dumps(conf, sort_keys=True))(
        base_key(seed))


# --------------------------------------------------------------------- rows


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _prefix(key, n, length, width):
    keys = jax.vmap(lambda r: jax.random.fold_in(key, r))(jnp.arange(n))
    return jax.vmap(lambda k: jax.random.normal(
        k, (length, width), jnp.float32))(keys)


class Feed:
    """Rows for the workers, from the seed.

    Token ids are drawn uniformly from the vocabulary on the host (numpy,
    keyed by (seed, worker, call, row)); the image prefix of a
    configuration with ``num_image_tokens`` is drawn on the device.  Every
    fetch is logged as (worker, call, rows asked for), so the rows of any
    round can be made again.
    """

    PREFIX_STREAM = 0x5EED

    def __init__(self, conf: dict, seq_len: int, seed: int):
        self.vocab = conf["vocab_size"]
        self.width = conf["hidden_size"]
        self.prefix_len = int(conf.get("num_image_tokens", 0))
        self.seq_len = seq_len
        self.seed = int(seed)
        self.calls: dict[int, int] = {}
        self.log: list[tuple[int, int, int]] = []

    def tokens(self, worker: int, call: int, n: int) -> np.ndarray:
        """(n, seq_len + 1) int32 ids; row r depends on r alone, not n."""
        seed = self.seed & (2**64 - 1)
        return np.stack([
            np.random.default_rng([seed, worker, call, r]).integers(
                0, self.vocab, self.seq_len + 1, dtype=np.int32)
            for r in range(n)])

    def prefix(self, worker: int, call: int, n: int):
        if not self.prefix_len:
            return None
        key = base_key(self.seed)
        for x in (self.PREFIX_STREAM, worker, call):
            key = jax.random.fold_in(key, x)
        return _prefix(key, n, self.prefix_len, self.width)

    def rows(self, worker: int, call: int, n: int) -> dict:
        ids = self.tokens(worker, call, n)
        batch = {"tokens": ids[:, :-1], "targets": ids[:, 1:]}
        prefix = self.prefix(worker, call, n)
        if prefix is not None:
            batch["prefix"] = prefix
        return batch

    def next_batch(self, worker: int, n: int) -> dict:
        call = self.calls.get(worker, 0)
        self.calls[worker] = call + 1
        self.log.append((worker, call, n))
        return self.rows(worker, call, n)

    def warm(self, n: int) -> None:
        """Compile the prefix maker for ``n`` rows (no call is logged)."""
        p = self.prefix(0, 0, n)
        if p is not None:
            p.block_until_ready()
