"""Faults planted in the timed path, for the tests and the control runs
that show the correctness check fails them (never used by a benchmark
run).  Each is a ``Plant``: ``workload`` rewrites the workload before the
trainer is built, ``session`` breaks the built session, ``undo`` restores
what was patched at module level.
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp

from bench import program


@dataclasses.dataclass
class Plant:
    name: str

    def workload(self, wl):
        return wl

    def session(self, session):
        pass

    def undo(self):
        pass


class Unchanged(Plant):
    """The update returns the parameters and optimizer state unchanged."""

    def session(self, session):
        program.replace_update(session, lambda p, g, s, step: (p, s))


class HalfBatch(Plant):
    """Each worker's gradient and loss are taken over the first half of its
    valid rows; the rest are left out of the mean."""

    def workload(self, wl):
        inner = wl.loss_and_grad

        def loss_and_grad(params, batch, mask):
            rows = jnp.arange(mask.shape[0])
            keep = rows < jnp.ceil(mask.sum() / 2)
            return inner(params, batch, mask * keep)

        return dataclasses.replace(wl, loss_and_grad=loss_and_grad)


class WrongCount(Plant):
    """Each worker's weight sum counts one padded row as valid, so its
    gradient and loss are divided by too many positions."""

    def workload(self, wl):
        inner = wl.loss_and_grad

        def loss_and_grad(params, batch, mask):
            (ls, ws, aux), g = inner(params, batch, mask)
            extra = batch["tokens"].shape[1] * jnp.ones_like(ws)
            return (ls, ws + extra, aux), g

        return dataclasses.replace(wl, loss_and_grad=loss_and_grad)


class DroppedWorker(Plant):
    """The combine leaves the last worker's gradient out (the exchange
    between workers, across slices on a mesh), weighting the others as if
    it had not existed."""

    def session(self, session):
        def wrap(combine):
            return lambda grads, batches: combine(grads[:-1], batches[:-1])

        self.undo = program.patch_combine(wrap)


FAULTS = {"unchanged": Unchanged, "half_batch": HalfBatch,
          "wrong_count": WrongCount, "dropped_worker": DroppedWorker}


def plant(name: str) -> Plant:
    return FAULTS[name](name)
