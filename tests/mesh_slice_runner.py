"""Subprocess helper: concurrent slice dispatch on the 8-fake-device debug
mesh (DESIGN.md §12).  Executed by test_backend.py in a fresh interpreter so
the XLA device-count flag can be set before jax initializes (the in-process
tier-1 suite runs on ONE device, which exercises the fallback path only).

Covers, on a real multi-device mesh: disjoint-slice placement, concurrent
BSP rounds (max-of-workers iteration time), ASP event flow, membership
slice replans, and checkpoint/resume bit-equivalence of controller +
measurement state.
"""

import os
import sys
import tempfile

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.api import (  # noqa: E402
    AddWorker,
    ClusterSpec,
    Experiment,
    MeshBackend,
    RemoveWorker,
    TrainConfig,
    paper_workload,
)
from repro.het.simulator import WorkerSpec  # noqa: E402
from repro.launch.mesh import make_debug_mesh  # noqa: E402
from repro.optim import sgd  # noqa: E402


def experiment(mesh, *, schedule=(), **cfg_kw):
    cfg = dict(b0=16, microbatch=4, batching="dynamic", max_steps=10, seed=0)
    cfg.update(cfg_kw)
    cluster = ClusterSpec.hlevel(
        39, 6, workload="mnist-cnn",
        backend=MeshBackend(mesh=mesh, dilation=[3.0, 1.5, 1.0]))
    if schedule:
        cluster = cluster.with_schedule(*schedule)
    return Experiment(
        workload=paper_workload("linreg"),
        cluster=cluster,
        optimizer=sgd(0.05),
        config=TrainConfig(**cfg),
    )


def controller_state(session):
    # exec_state_dict IS the product's mesh checkpoint surface (incl. the
    # slice plan), so this comparison tracks it field-for-field
    t = session.trainer
    return {
        "step": t.step_idx,
        "batches": list(t.batches),
        "controller": t.controller.state_dict(),
        "exec": t.exec_state_dict(),
        "engine": (t.engine.version, list(t.engine.read_version)),
    }


class _RecordingSource:
    """Wraps a workload's next_batch, recording what each call returned so
    the gradient-exactness check can build the unpadded reference from the
    SAME examples."""

    def __init__(self, next_batch):
        self.next_batch = next_batch
        self.fetched = []

    def __call__(self, worker, n):
        batch = self.next_batch(worker, n)
        self.fetched.append(batch)
        return batch


def check_slice_gradient_exactness(mesh) -> None:
    """The PR-3 ragged-gradient property, on DISJOINT slices: bucketed
    padding + masking + per-slice ``weighted_psum`` + lambda-combine must
    equal the unpadded ``combine_weighted`` reference over the same
    examples — i.e. slicing the mesh does not perturb Eq. 2-3."""
    from repro.core import combine_weighted
    from repro.train.loop import TrainConfig
    from repro.train.mesh import MeshTrainer

    wl = paper_workload("linreg")
    src = _RecordingSource(wl.next_batch)
    trainer = MeshTrainer(
        mesh=mesh, num_workers=3, init_params=wl.init,
        loss_and_grad=wl.loss_and_grad, next_batch=src,
        optimizer=sgd(0.05),
        cfg=TrainConfig(b0=16, microbatch=4, batching="uniform",
                        max_steps=5))
    assert trainer.concurrent and len({r.mesh for r in trainer._exec}) == 3
    for batches in ([5, 17, 29], [1, 2, 3], [31, 8, 19]):
        mesh_grads, ref_grads = [], []
        for k, b in enumerate(batches):
            src.fetched.clear()
            g_mesh, ls, ws, _t = trainer._measured_worker_grad(k, b)
            assert abs(ws - b) < 1e-6       # mask weight == real examples
            (padded,) = src.fetched
            sliced = jax.tree_util.tree_map(lambda x: x[:b], padded)
            import jax.numpy as jnp
            (ls_ref, ws_ref, _aux), g_sum = wl.loss_and_grad(
                trainer.params, sliced, jnp.ones((b,), jnp.float32))
            assert abs(float(ls_ref) - ls) < 1e-4 * max(abs(ls), 1.0)
            ref_grads.append(jax.tree_util.tree_map(lambda g: g / b, g_sum))
            mesh_grads.append(jax.device_get(g_mesh))
        combined_mesh = combine_weighted(mesh_grads, batches)
        combined_ref = combine_weighted(ref_grads, batches)
        for lm, lr in zip(jax.tree_util.tree_leaves(combined_mesh),
                          jax.tree_util.tree_leaves(combined_ref)):
            np.testing.assert_allclose(np.asarray(lm), np.asarray(lr),
                                       rtol=1e-5, atol=1e-6)


def main() -> int:
    assert len(jax.devices()) == 8, jax.devices()
    mesh = make_debug_mesh(8)

    # ---- gradient exactness over disjoint slices (Eq. 2-3 preserved) ----
    check_slice_gradient_exactness(mesh)

    # ---- concurrent BSP: disjoint slices, max-of-workers rounds ----
    session = experiment(mesh).session()
    trainer = session.trainer
    assert trainer.concurrent, "4-wide data axis must give concurrent mode"
    plan = trainer.slice_plan
    covered = sorted(i for w in range(plan.k) for i in plan.devices_of(w))
    assert covered == list(range(plan.extent)), covered   # disjoint+exhaustive
    assert [r.quantum for r in trainer._exec] == plan.lengths
    out = session.run()
    assert out["steps"] == 10
    for rec in out["history"]:
        assert rec.worker_times and len(rec.worker_times) == 3
        assert abs(rec.iteration_time - max(rec.worker_times)) < 1e-12, \
            "BSP round must cost max-of-workers, not sum"
    assert out["final_loss"] < out["history"][0].loss
    # each worker's outputs of the last round live on its own slice's rows
    rows = np.asarray(mesh.devices)
    for w, (start, length) in enumerate(plan.slices):
        want = frozenset(rows[start:start + length].flat)
        assert trainer.last_round_devices[w] == {want}, \
            (w, trainer.last_round_devices[w], want)

    # ---- checkpoint/resume bit-equivalence on the debug mesh ----
    path = os.path.join(tempfile.mkdtemp(), "ckpt")
    s1 = experiment(mesh).session()
    for i, _rec in enumerate(s1):
        if i == 5:
            break
    s1.save(path)
    s2 = experiment(mesh).session()
    s2.restore(path)
    a, b = controller_state(s1), controller_state(s2)
    assert a == b, f"controller state not bit-identical:\n{a}\n{b}"
    for la, lb in zip(jax.tree_util.tree_leaves(s1.params),
                      jax.tree_util.tree_leaves(s2.params)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
    out2 = s2.run()
    assert out2["steps"] == 10 and s2.trainer.step_idx == 10

    # ---- ASP on the mesh: event-ordered updates, staleness recorded ----
    out_asp = experiment(mesh, sync="asp", max_steps=12).run()
    assert out_asp["steps"] == 12
    stale = [r.straggler_waste for r in out_asp["history"]]
    assert max(stale) >= 1 and all(s >= 0 for s in stale), stale
    b_asp = out_asp["final_batches"]
    assert sum(b_asp) == sum(out_asp["history"][0].batches)

    # ---- membership: slice replan keeps invariants ----
    sched = (RemoveWorker(step=3, worker=0),
             AddWorker(step=6, spec=WorkerSpec(cores=12)))
    s4 = experiment(mesh, schedule=sched, b0=8, max_steps=9).session()
    out4 = s4.run()
    assert out4["steps"] == 9
    plan4 = s4.trainer.slice_plan
    covered = sorted(i for w in range(plan4.k) for i in plan4.devices_of(w))
    assert covered == list(range(plan4.extent))
    assert sum(out4["final_batches"]) == sum(out4["history"][0].batches)

    print("mesh_slice_runner: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
