"""The trainer's spans and counters (``repro.train.spans``): names, nesting
and identifiers in a profiler trace read back with ``ProfileData``, the
round's phase table, the update's program name, the compile counter, and
``MeshTrainer``'s phases and counters on the one-device mesh (sequential
fallback) and, in a fresh interpreter, on an 8-device mesh where the
workers run concurrently on slices and their gradients cross them."""

import glob
import json
import os
import subprocess
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData

from repro.api import paper_workload
from repro.launch.mesh import make_data_mesh
from repro.optim import sgd
from repro.train import spans
from repro.train.loop import TrainConfig
from repro.train.mesh import MeshTrainer


def host_spans(log_dir):
    """[name, start, end, line index, stats] of every ``repro.*`` host
    event in the one trace under ``log_dir``."""
    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                        recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                out += [[e.name, e.start_ns, e.start_ns + e.duration_ns, i,
                         {k: v for k, v in e.stats}]
                        for e in line.events if e.name.startswith("repro.")]
    return out


def test_spans_land_in_the_trace_named_nested_and_identified(tmp_path):
    phases = {}
    jax.profiler.start_trace(str(tmp_path))
    try:
        with ThreadPoolExecutor(1) as pool, \
                spans.span("dispatch", phases, round=3, worker=1):
            with spans.span("update", phases, round=3):
                x = jnp.ones(8) * 2
            seconds = pool.submit(spans.span_until_ready, "transfer", x,
                                  time.perf_counter(), round=3
                                  ).result(timeout=30)
    finally:
        jax.profiler.stop_trace()
    got = {s[0]: s for s in host_spans(tmp_path)}
    assert set(got) == {"repro.dispatch", "repro.update", "repro.transfer"}
    dispatch, update, transfer = (got["repro." + n] for n in
                                  ("dispatch", "update", "transfer"))
    assert dispatch[4] == {"round": 3, "worker": 1}
    assert update[4] == transfer[4] == {"round": 3}
    # update nests in dispatch on the same thread; the transfer ran on the
    # pool's thread, inside the dispatch span's time
    assert update[3] == dispatch[3] != transfer[3]
    assert dispatch[1] <= update[1] and update[2] <= dispatch[2]
    assert dispatch[1] <= transfer[1] and transfer[2] <= dispatch[2]
    # the phase table holds the main thread's spans, the outer one whole
    assert set(phases) == {"dispatch", "update"}
    assert phases["dispatch"] >= phases["update"] > 0
    assert seconds > 0


def test_span_seconds_add_up_by_name_and_cost_little_untraced():
    phases = {}
    for _ in range(3):
        with spans.span("control", phases, round=0):
            time.sleep(0.002)
    assert phases["control"] >= 0.006
    with spans.span("control"):       # no table: the annotation alone
        pass
    assert set(phases) == {"control"}
    n = 2000
    t = time.perf_counter()
    for i in range(n):
        with spans.span("dispatch", phases, round=i, worker=0):
            pass
    # no profiler runs: a few microseconds each at most, even on a loaded
    # CPU (about one on an idle one)
    assert (time.perf_counter() - t) / n < 200e-6


@pytest.mark.parametrize("coupled", [False, True])
def test_update_program_is_jit_update(coupled):
    """The benchmark finds the optimizer update on the device by its program
    name, ``jit_update``: the trainer's fixed-LR update and each per-scale
    update of a batch-coupled LR."""
    from repro.api import ClusterSpec, Experiment, SimBackend
    from repro.optim import batch_coupled
    from repro.core import GlobalBatchConfig

    if coupled:
        t = Experiment(
            workload=paper_workload("linreg"),
            cluster=ClusterSpec.hlevel(24, 3.0, 3, workload="linreg", seed=0,
                                       backend=SimBackend()),
            optimizer=sgd(batch_coupled(0.02, rule="linear")),
            config=TrainConfig(b0=4, microbatch=4, batching="dynamic",
                               max_steps=4, seed=0,
                               global_batch=GlobalBatchConfig(kind="gns"))
        ).session().trainer
        t._apply_global_batch(30)
        assert set(t._opt_jit_cache) == {1.0, 2.5}
    else:
        t = one_device_trainer()
    p = {"w": jnp.ones((2,))}
    lowered = t._opt_update.lower(p, p, (), jnp.asarray(0))
    assert "module @jit_update" in lowered.as_text()


def test_compiles_counts_backend_compiles():
    n0, s0 = spans.compiles()
    jax.jit(lambda x: x * 3 + 1)(jnp.ones(5)).block_until_ready()
    n1, s1 = spans.compiles()
    assert n1 >= n0 + 1 and s1 > s0


def test_compile_listener_counts_from_threads():
    """The listener's counts are updated under a lock: compiles on several
    threads at once are all counted."""
    n0, _ = spans.compiles()
    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(
            target=lambda k=k: jax.jit(lambda x: x + k)(
                jnp.ones(3)).block_until_ready()) for k in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(switch)
    assert spans.compiles()[0] >= n0 + 8


# ----------------------------------------------------------- the trainer


BATCHES = [5, 17, 29]


def one_device_trainer():
    wl = paper_workload("linreg")
    t = MeshTrainer(
        mesh=make_data_mesh(1), num_workers=3, init_params=wl.init,
        loss_and_grad=wl.loss_and_grad, next_batch=wl.next_batch,
        optimizer=sgd(0.05),
        cfg=TrainConfig(b0=16, microbatch=4, batching="uniform",
                        max_steps=5))
    t.batches = list(BATCHES)
    return t


def test_sequential_round_phases_and_counters():
    """The one-device fallback: the round's phases tile its host time
    with no exchange and no transfer; the row counters are what the
    ladder and the split imply."""
    t = one_device_trainer()
    assert not t.concurrent
    for _ in range(2):
        v0, p0 = t.rows_valid, t.rows_padded
        a = time.perf_counter()
        rec = t.bsp_step()
        wall = time.perf_counter() - a
        assert set(rec.phases) >= {"dispatch", "await", "combine",
                                   "update", "control"}
        assert not {"exchange", "transfer"} & set(rec.phases)
        assert 0 < sum(rec.phases.values()) <= wall
        assert t.rows_valid - v0 == sum(BATCHES)
        assert t.rows_padded - p0 == sum(
            t.bucket_for(k, b) - b for k, b in enumerate(BATCHES))
    assert t.exchange_bytes == 0
    # the first round traced and re-ran each worker once for timing
    assert [r.phases.get("rerun", 0) > 0 for r in t.history] == [True, False]


def test_asp_and_sim_records_carry_no_phases():
    from repro.api import ClusterSpec, Experiment, MeshBackend, SimBackend

    for backend, sync in ((MeshBackend(), "asp"), (SimBackend(), "bsp")):
        out = Experiment(
            workload=paper_workload("linreg"),
            cluster=ClusterSpec.hlevel(39, 6, workload="mnist-cnn",
                                       backend=backend),
            optimizer=sgd(0.05),
            config=TrainConfig(b0=16, microbatch=4, batching="dynamic",
                               max_steps=3, seed=0, sync=sync)).run()
        assert [r.phases for r in out["history"]] == [None] * 3


def test_trainer_counts_its_compiles():
    t = one_device_trainer()
    c0 = t.compiles
    t.bsp_step()
    assert t.compiles > c0 and t.compile_s > 0


# ----------------------------------------------- 8 devices, concurrent


@pytest.fixture(scope="module")
def eight_devices():
    here = os.path.dirname(os.path.abspath(__file__))
    proc = subprocess.run(
        [sys.executable, os.path.join(here, "spans_runner.py")],
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, \
        f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    (line,) = [x for x in proc.stdout.splitlines()
               if x.startswith("spans_runner: ")]
    return json.loads(line[len("spans_runner: "):])


@pytest.mark.slow
@pytest.mark.subprocess
@pytest.mark.parametrize("i", [0, 1])
def test_concurrent_round_has_a_transfer_and_counts_moved_bytes(
        eight_devices, i):
    """Each concurrent round's gradients cross slices: the transfer phase
    joins the record, and ``exchange_bytes`` grows by each leaf's bytes
    times the chips it was moved to (the mesh's 8 less its slice's)."""
    r = eight_devices["concurrent"][i]
    assert r["phases"]["transfer"] > 0 and r["phases"]["exchange"] > 0
    main = sum(v for k, v in r["phases"].items() if k != "transfer")
    assert 0 < main <= r["wall"]
    assert r["exchange_bytes"] == r["expected_bytes"] > 0
    lengths = [n for _, n in eight_devices["slices"]]
    assert sum(lengths) == 8 and len(lengths) == 3


@pytest.mark.slow
@pytest.mark.subprocess
@pytest.mark.parametrize("mode", ["concurrent", "sequential"])
def test_rows_counters_follow_the_ladder(eight_devices, mode):
    rounds = eight_devices[mode]
    for r in rounds if isinstance(rounds, list) else [rounds]:
        assert r["rows_valid"] == sum(BATCHES)
        assert r["rows_padded"] == r["expected_padded"] > 0


@pytest.mark.slow
@pytest.mark.subprocess
def test_sequential_round_on_eight_devices_moves_nothing(eight_devices):
    r = eight_devices["sequential"]
    assert "transfer" not in r["phases"] and "exchange" not in r["phases"]
    assert r["exchange_bytes"] == 0


@pytest.mark.slow
@pytest.mark.subprocess
def test_traced_round_spans_threads_and_ids(eight_devices):
    """Under the profiler: one span per worker dispatch with its worker
    id, every span of the round carrying the round index, the transfer on
    a thread of its own and the rest on the thread that holds the window."""
    traced = [s for s in eight_devices["traced"]
              if s[0].startswith("repro.")]
    names = [s[0] for s in traced]
    assert names.count("repro.dispatch") == 3
    assert names.count("repro.transfer") == 1
    assert {"repro.await", "repro.exchange", "repro.combine",
            "repro.update", "repro.control"} <= set(names)
    assert {s[4]["round"] for s in traced} == {2}
    assert sorted(s[4]["worker"] for s in traced
                  if s[0] == "repro.dispatch") == [0, 1, 2]
    for name, _, _, on_main, _ in traced:
        assert on_main == (name != "repro.transfer"), name
    assert eight_devices["compiles"] > 0


@pytest.mark.slow
@pytest.mark.subprocess
def test_pending_transfer_holds_no_awaiter(eight_devices):
    """The transfer span runs on a thread of its own: with four workers (as
    many as the smallest awaiter pool has threads) and a round's transfer
    held pending, the next round's four awaiters all run at once, so none
    stamps its worker's completion late behind the transfer."""
    r = eight_devices["pending_transfer"]
    assert r["workers"] == 4
    assert r["next_round_done"] and r["errors"] == []
    assert r["transfer_pending"]
    assert len(r["stamps"]) == 4
    assert all(done < r["released"] for _, done in r["stamps"])
