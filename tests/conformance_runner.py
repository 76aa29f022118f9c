"""Subprocess helper: cross-backend outer-loop conformance (ISSUE 10).

Executed by test_conformance.py in a fresh interpreter so the
8-fake-device XLA flag lands before jax initializes.  Runs the SAME
seeded Experiment — every outer kind (fixed/gns/bandit/dynamix) crossed
with a static-membership BSP schedule and an elastic remove/add schedule
— on ``SimBackend`` and the debug-mesh ``MeshBackend``, and prints one
JSON document with each run's *discrete* outer trajectory:

  * the per-step batch split (and hence Σb_k) for every round,
  * the outer controller's rung walk, resize log and resize count,
  * the bandit's arm counts / the dynamix policy's action log.

Float state (losses, EWMAs, Q-weights) is intentionally excluded: the
two backends compute the same reductions in different orders, so floats
agree only to ULPs — the conformance contract is that the DECISIONS are
bit-identical.  Three things make that well-defined (DESIGN.md §18):

  * the geometry is chosen so both backends feed ``next_batch`` the SAME
    padded sizes (the data stream is a pure function of (seed, worker,
    call, n)): 2 workers x 4 devices, microbatch 4, mesh ladder growth
    2.0, outer ladder [16, 32, 64] with even splits — every per-worker
    batch (8/16/32, or 16/32/64 solo after the removal) is an exact rung
    of BOTH the sim microbatch grid and the mesh bucket ladder, so
    neither backend ever pads;
  * ``time_signal='steps'`` removes measured wall-clock from the
    bandit/dynamix reward and features;
  * the dynamix feature/reward quantization (1e-3) absorbs the residual
    ULP-level (reduction-order) loss differences.

Elastic legs pin the post-event split with an ``At`` event: the two
backends intentionally replan membership from different signals (sim
peeks its throughput model, mesh uses measured rates), so the pin
isolates the outer loop under test from that known divergence.
"""

import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.api import (  # noqa: E402
    AddWorker,
    At,
    ClusterSpec,
    Experiment,
    MeshBackend,
    RemoveWorker,
    SimBackend,
    TrainConfig,
    paper_workload,
)
from repro.core import GlobalBatchConfig  # noqa: E402
from repro.het.simulator import WorkerSpec  # noqa: E402
from repro.launch.mesh import make_debug_mesh  # noqa: E402
from repro.optim import batch_coupled, sgd  # noqa: E402

STEPS = 14
B0 = 8                       # per worker -> B_global = 16, rungs [16, 32, 64]
FLEET = [WorkerSpec(cores=12.0), WorkerSpec(cores=8.0)]

KINDS = ("fixed", "gns", "bandit", "dynamix")


def outer_cfg(kind: str) -> GlobalBatchConfig:
    common = dict(warmup=4, cooldown=2, ladder_growth=2.0, max_factor=4.0,
                  seed=0)
    if kind == "fixed":
        return GlobalBatchConfig()
    if kind == "gns":
        return GlobalBatchConfig(kind="gns", gns_min_samples=2, **common)
    if kind == "bandit":
        return GlobalBatchConfig(kind="bandit", bandit_window=3,
                                 time_signal="steps", **common)
    # seed 3: at seed 0 the policy holds on every decision of this run,
    # which would leave the dynamix legs vacuous (no resize to compare);
    # which seeds move is an open debt (DESIGN.md §18, ROADMAP.md §3)
    return GlobalBatchConfig(kind="dynamix", bandit_window=3,
                             gns_min_samples=2, time_signal="steps",
                             **{**common, "seed": 3})


def _even_split(total: int, k: int) -> list:
    base, extra = divmod(total, k)
    return [base + (1 if i < extra else 0) for i in range(k)]


def _pin(trainer) -> None:
    """Pin the split to the deterministic even apportionment of the
    CURRENT B_global (sum is preserved — only the shares move)."""
    trainer.batches = _even_split(sum(trainer.batches), trainer.k)


def schedule(elastic: bool):
    if not elastic:
        return ()
    # same-step events apply in the order given: the membership change
    # first, then the pin that re-splits whatever B_global is current
    return (RemoveWorker(step=6, worker=1), At(step=6, fn=_pin),
            AddWorker(step=10, spec=WorkerSpec(cores=8.0)),
            At(step=10, fn=_pin))


def run_case(kind: str, elastic: bool, backend) -> dict:
    cluster = ClusterSpec.explicit(list(FLEET), workload="linreg", seed=0,
                                   backend=backend)
    evs = schedule(elastic)
    if evs:
        cluster = cluster.with_schedule(*evs)
    exp = Experiment(
        workload=paper_workload("linreg"),
        cluster=cluster,
        optimizer=sgd(batch_coupled(0.05, rule="linear")),
        config=TrainConfig(b0=B0, microbatch=4, batching="uniform",
                           max_steps=STEPS, seed=0,
                           global_batch=outer_cfg(kind)),
    )
    session = exp.session()
    out = session.run()
    t = session.trainer
    traj = {
        "batches": [list(rec.batches) for rec in out["history"]],
        "b_global": [sum(rec.batches) for rec in out["history"]],
    }
    if t.outer is not None:
        st = t.outer.state_dict()
        traj.update(rung=st["rung"], rungs=st["rungs"],
                    step_count=st["step_count"],
                    num_resizes=st["num_resizes"],
                    resize_log=st["resize_log"])
        if kind == "bandit":
            traj["arm_counts"] = st["extra"]["counts"]
        if kind == "dynamix":
            traj["action_log"] = st["extra"]["action_log"]
            traj["decisions"] = st["extra"]["decisions"]
    return traj


def main() -> int:
    import jax
    assert len(jax.devices()) == 8, jax.devices()
    mesh = make_debug_mesh(8)
    results = {}
    for kind in KINDS:
        for elastic in (False, True):
            name = f"{kind}-{'elastic' if elastic else 'bsp'}"
            results[name] = {
                "sim": run_case(kind, elastic, SimBackend()),
                "mesh": run_case(kind, elastic,
                                 MeshBackend(mesh=mesh, growth=2.0,
                                             dilation="from-spec")),
            }
    print("CONFORMANCE_JSON_BEGIN")
    print(json.dumps(results))
    print("CONFORMANCE_JSON_END")
    return 0


if __name__ == "__main__":
    sys.exit(main())
