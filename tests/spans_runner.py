"""Subprocess helper: the mesh trainer's spans and counters on an 8-device
CPU mesh.  Executed by test_spans.py in a fresh interpreter, so the XLA
device-count flag can be set before jax initializes.

Runs two concurrent BSP rounds of three workers on slices of a 1-D
8-device data axis, the second under the profiler, one round of the
sequential fallback on the same mesh, and four concurrent workers whose
round's transfer is held pending through the next round; prints one JSON
line of what the tests check, after ``spans_runner:``.
"""

import glob
import json
import os
import sys
import tempfile
import threading
import time

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402
from jax.profiler import ProfileData  # noqa: E402

from repro.api import paper_workload  # noqa: E402
from repro.launch.mesh import make_data_mesh  # noqa: E402
from repro.optim import sgd  # noqa: E402
from repro.train import mesh as mesh_module  # noqa: E402
from repro.train import spans  # noqa: E402
from repro.train.loop import TrainConfig  # noqa: E402
from repro.train.mesh import MeshTrainer  # noqa: E402

BATCHES = [5, 17, 29]


def trainer(mesh, concurrent: bool, workers: int = 3) -> MeshTrainer:
    wl = paper_workload("linreg")
    t = MeshTrainer(
        mesh=mesh, num_workers=workers, init_params=wl.init,
        loss_and_grad=wl.loss_and_grad, next_batch=wl.next_batch,
        optimizer=sgd(0.05), concurrent=concurrent,
        cfg=TrainConfig(b0=16, microbatch=4, batching="uniform",
                        max_steps=5))
    if workers == len(BATCHES):
        t.batches = list(BATCHES)
    return t


def round_facts(t: MeshTrainer) -> dict:
    """One BSP round: its phases once any transfer has joined, its wall
    seconds and the counters' deltas."""
    before = (t.exchange_bytes, t.rows_valid, t.rows_padded)
    a = time.perf_counter()
    rec = t.bsp_step()
    wall = time.perf_counter() - a
    jax.block_until_ready(t.params)
    deadline = time.monotonic() + 30
    while t.concurrent and "transfer" not in rec.phases \
            and time.monotonic() < deadline:
        time.sleep(0.01)
    return {
        "phases": rec.phases, "wall": wall,
        "exchange_bytes": t.exchange_bytes - before[0],
        "rows_valid": t.rows_valid - before[1],
        "rows_padded": t.rows_padded - before[2],
        "expected_padded": sum(t.bucket_for(k, b) - b
                               for k, b in enumerate(BATCHES)),
        "expected_bytes": sum(
            x.nbytes for x in jax.tree.leaves(t.params)) * sum(
            len(jax.devices()) - rec_.mesh.devices.size for rec_ in t._exec),
    }


def traced_spans(t: MeshTrainer) -> list:
    """A round under the profiler, inside a ``bench.window`` annotation:
    the program's spans as [name, start, duration, on the window's thread,
    stats]."""
    log_dir = tempfile.mkdtemp()
    jax.profiler.start_trace(log_dir)
    with jax.profiler.TraceAnnotation("bench.window"):
        t.bsp_step()
        jax.block_until_ready(t.params)
        time.sleep(0.2)                 # the transfer span closes
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                        recursive=True)
    lines = [list(line.events) for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:") for line in plane.lines]
    (main,) = [i for i, events in enumerate(lines)
               if any(e.name == "bench.window" for e in events)]
    return [[e.name, e.start_ns, e.duration_ns, i == main, dict(e.stats)]
            for i, events in enumerate(lines) for e in events
            if e.name.startswith("repro.")]


def pending_transfer(mesh) -> dict:
    """Four concurrent workers, as many as the smallest awaiter pool has
    threads: a round whose transfer is held pending, then the next round,
    whose four awaiters must all run at once (none queued behind the held
    transfer, to stamp its worker's completion late)."""
    t = trainer(mesh, concurrent=True, workers=4)
    t.bsp_step()                        # traces, compiles and re-runs
    release = threading.Event()
    together = threading.Barrier(t.k, timeout=20)
    real_transfer = spans.span_until_ready
    real_stamp = mesh_module._ready_timestamp

    def held(*args, **ids):
        release.wait(60)
        return real_transfer(*args, **ids)

    def stamp(out):
        together.wait()                 # every awaiter of the round at once
        return real_stamp(out)

    errors = []

    def next_round():
        try:
            t.bsp_step()
        except Exception as e:          # noqa: BLE001 - reported
            errors.append(repr(e))

    spans.span_until_ready = held
    try:
        first = t.bsp_step()
        mesh_module._ready_timestamp = stamp
        nxt = threading.Thread(target=next_round, daemon=True)
        nxt.start()
        nxt.join(timeout=60)
        out = {"next_round_done": not nxt.is_alive(), "errors": errors,
               "transfer_pending": "transfer" not in first.phases,
               "stamps": t.last_round_stamps, "released": time.perf_counter(),
               "workers": t.k}
    finally:
        spans.span_until_ready = real_transfer
        mesh_module._ready_timestamp = real_stamp
        release.set()
    return out


def main() -> int:
    assert len(jax.devices()) == 8, jax.devices()
    mesh = make_data_mesh(8)
    t = trainer(mesh, concurrent=True)
    assert t.concurrent
    out = {"slices": [list(s) for s in t.slice_plan.slices],
           "concurrent": [round_facts(t), round_facts(t)],
           "traced": traced_spans(t),
           "compiles": t.compiles, "compile_s": t.compile_s}
    s = trainer(mesh, concurrent=False)
    assert not s.concurrent
    out["sequential"] = round_facts(s)
    out["pending_transfer"] = pending_transfer(mesh)
    print("spans_runner: " + json.dumps(out))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
