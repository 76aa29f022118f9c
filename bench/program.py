"""The benchmark's one adapter to the system under test.

Everything the benchmark asks of the trainer beyond ``repro.api``'s public
surface (``Experiment``, ``Session.step``, ``Session.batches``,
``MeshTrainer.bucket_for``, ``StepRecord``) sits here: building the
session from a cell's traffic file and the ModelConfig that its
architecture module maps the configuration to, running a worker's
compiled step at a bucket without a round (``warm``), Adam's first
moment (``first_moment``), the trainer's trace counter (``traces``), the
chips it holds (``devices``) and the two places the planted faults break
(``replace_update``, ``patch_combine``).  A program change that moves
one of these edits this file alone; PERF.md lists the public hooks that
would let it go.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np


class _FeedSource:
    """The data-source surface ``lm_workload`` asks of a pipeline."""

    def __init__(self, next_batch):
        self.next_batch = next_batch

    def state_dict(self):
        return {}

    def load_state_dict(self, state):
        pass


def build_session(model, traffic: dict, seed: int, params, next_batch,
                  observe=None, plant=None):
    """The trainer behind ``repro.api`` for the program's ModelConfig
    ``model`` (the architecture module's ``program_config``), built with
    the run's weights and its rows from ``next_batch(worker, n)``, as the
    cell's traffic file describes it.  ``observe`` wraps the controller's
    ``observe``; ``plant`` (tests and the control runs only,
    ``bench/faults.py``) breaks the timed path."""
    from repro.api import (ClusterSpec, Experiment, MeshBackend,
                           TrainConfig, lm_workload)
    from repro.core import ControllerConfig
    from repro.launch.mesh import make_data_mesh
    from repro.optim import adam

    workload = dataclasses.replace(
        lm_workload(model, _FeedSource(next_batch), use_kernel=True),
        init=lambda key: params)
    if plant is not None:
        workload = plant.workload(workload)
    c = traffic["cluster"]
    backend = MeshBackend(mesh=make_data_mesh(traffic["devices"]),
                          dilation=traffic["dilation"],
                          concurrent=traffic["concurrent"])
    if c["kind"] == "hlevel":
        cluster = ClusterSpec.hlevel(c["total_cores"], c["h_level"],
                                     traffic["workers"],
                                     workload=c["sim_workload"],
                                     backend=backend)
    else:
        cluster = ClusterSpec.homogeneous(c["total_cores"],
                                          traffic["workers"],
                                          workload=c["sim_workload"],
                                          backend=backend)
    o = traffic["optimizer"]
    if o["name"] != "adam":
        raise ValueError(f"optimizer {o['name']!r}: the check reads Adam's "
                         f"first moment")
    config = TrainConfig(
        b0=traffic["b0"], microbatch=traffic["microbatch"],
        batching=traffic["batching"],
        controller=ControllerConfig(**traffic["controller"]),
        max_steps=2**62, seed=seed & 0x7FFFFFFF)
    session = Experiment(
        workload=workload, cluster=cluster,
        optimizer=adam(o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"]),
        config=config).session()
    del params      # the trainer holds them now; the closure must not
    ctrl = session.trainer.controller
    if ctrl is not None and observe is not None:
        ctrl.observe = observe(ctrl.observe)
    if plant is not None:
        plant.session(session)
    return session


def warm(trainer, rows, batches) -> int:
    """Run every worker's gradient step once at every bucket that a batch
    in ``batches`` maps to and that has not run yet, on ``rows(worker,
    n)``, the way a round dispatches it; parameters and optimizer state
    are left as they were.  Returns how many steps ran."""
    done: dict[int, set] = {}
    for k, rec in enumerate(trainer._exec):
        done.setdefault(id(rec), set()).update(trainer.worker_buckets[k])
    warmed = 0
    for k, rec in enumerate(trainer._exec):
        for b in batches:
            n = trainer.bucket_for(k, b)
            if n in done[id(rec)]:
                continue
            done[id(rec)].add(n)
            data = jax.tree.map(
                lambda x: jax.device_put(x, rec.data_sharding), rows(k, n))
            mask = jax.device_put(jnp.ones((n,), jnp.float32),
                                  rec.data_sharding)
            params = jax.device_put(trainer.params, rec.params_sharding)
            jax.block_until_ready(rec.gradfn(params, data, mask))
            warmed += 1
    return warmed


def first_moment(trainer):
    """Adam's first moment, a tree like the parameters."""
    return trainer.opt_state["m"]


def traces(trainer) -> int:
    """XLA traces of the worker step so far."""
    return trainer.accum_traces


def devices(trainer) -> list:
    """The chips the trainer's mesh holds."""
    return list(np.ravel(trainer.mesh.devices))


def replace_update(session, update) -> None:
    """Put ``update(params, grad, opt_state, step) -> (params,
    opt_state)`` in the place of the trainer's optimizer update."""
    session.trainer._opt_update = update


def patch_combine(wrap):
    """Put ``wrap(combine)`` in the place of the combine of the workers'
    gradients; returns the function that undoes it."""
    from repro.train import mesh

    orig = mesh.combine_weighted
    mesh.combine_weighted = wrap(orig)

    def undo():
        mesh.combine_weighted = orig
    return undo
