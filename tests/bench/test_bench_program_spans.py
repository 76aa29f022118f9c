"""The trainer's own spans in a trace (``bench/program_spans.py``): idle
time by label on hand-made events, the three shares on hand-made traces,
and the loader on a real CPU trace of a tiny cell run through the
harness."""

from __future__ import annotations

import gzip
import json

import pytest

from _tiny import tiny_root

from bench import harness, program_spans as ps, trace_reduce as tr  # noqa
from bench.peaks import PEAKS  # noqa: E402


def hand_made(chips: int = 2, program: bool = True) -> dict:
    """One round in [0, 100] ns: the driving thread "m" runs dispatch (the
    feed inside it), await, exchange, combine, update and control; another
    thread "a" holds the transfer; each chip runs the same ops,
    the update's op inside a ``jit_update`` program."""
    host = [["bench.window", 0, 100, "m", {}], ["bench.round", 0, 80, "m", {}],
            ["bench.fetch", 5, 5, "m", {}]]
    if program:
        host += [["repro.dispatch", 0, 20, "m", {"round": 0, "worker": 0}],
                 ["repro.await", 20, 30, "m", {"round": 0}],
                 ["repro.exchange", 50, 5, "m", {"round": 0}],
                 ["repro.transfer", 55, 20, "a", {"round": 0}],
                 ["repro.combine", 56, 4, "m", {"round": 0}],
                 ["repro.update", 60, 2, "m", {"round": 0}],
                 ["repro.control", 62, 8, "m", {"round": 0}]]
    host.sort(key=lambda h: h[1])
    ops = [["w", 12, 8], ["w", 25, 20], ["u", 70, 5], ["x", 85, 15]]
    planes = [f"/device:TPU:{i}" for i in range(chips)]
    return {"window": [0, 100],
            "devices": {p: list(ops) for p in planes},
            "spans": [h[:3] for h in host if h[0].startswith("bench.")],
            "host": host,
            "modules": {p: [["jit_update(7)", 70, 5]] for p in planes}}


def test_idle_is_put_down_to_the_transfer_first_then_the_driving_thread():
    trace = hand_made()
    idle = ps.idle_by_label(trace)
    want = {"repro.dispatch": 7, "bench.fetch": 5, "repro.await": 10,
            "repro.exchange": 5, "repro.transfer": 15, "bench.round": 5,
            "outside": 5}
    assert idle == {k: pytest.approx(v * 1e-9) for k, v in want.items()}
    # the labels add up to the window less busy time, as the harness's do
    s = tr.reduce(trace)
    assert sum(idle.values()) == pytest.approx(s.window_s - s.busy_s)
    assert sum(s.idle_s.values()) == pytest.approx(sum(idle.values()))


def test_spans_on_other_threads_label_nothing_but_the_transfer():
    trace = hand_made()
    trace["host"].append(["repro.update", 80, 20, "a", {"round": 0}])
    idle = ps.idle_by_label(trace)
    assert idle["outside"] == pytest.approx(5e-9)
    assert "repro.update" not in idle


def test_shares_on_a_hand_made_trace():
    trace = hand_made()
    # idle under dispatch (7) and exchange (5), not the feed inside dispatch
    assert ps.host_idle_share(trace) == pytest.approx(12.0)
    assert ps.slice_transfer_share(trace) == pytest.approx(15.0)
    assert ps.update_time_share(trace) == pytest.approx(100 * 5 / 48)


def test_shares_where_there_is_nothing_to_read():
    """One chip has no cross-slice transfer; a program without spans (the
    trace a parent commit gives) has no host or transfer share; a trace
    without the update's program has no update share."""
    one = hand_made(chips=1)
    assert ps.slice_transfer_share(one) is None
    assert ps.host_idle_share(one) == pytest.approx(12.0)
    bare = hand_made(program=False)
    assert ps.host_idle_share(bare) is None
    assert ps.slice_transfer_share(bare) is None
    assert ps.update_time_share(bare) == pytest.approx(100 * 5 / 48)
    bare["modules"] = {}
    assert ps.update_time_share(bare) is None


def test_excerpt_keeps_threads_stats_and_programs():
    trace = hand_made()
    trace["window"] = [0, 100]
    cut = ps.excerpt(trace, 61.5e-9)
    assert cut["window"] == [0, 61]
    assert ps.main_thread(cut) == "m"
    names = {h[0] for h in cut["host"]}
    assert {"repro.dispatch", "repro.transfer", "repro.update"} <= names
    assert "repro.control" not in names           # starts at 62
    assert [h[4] for h in cut["host"] if h[0] == "repro.dispatch"] == [
        {"round": 0, "worker": 0}]
    assert cut["modules"] == {p: [] for p in trace["modules"]}


def test_tiny_traced_run_keeps_the_programs_spans(tmp_path, monkeypatch,
                                                  capsys):
    """A traced tiny cell on the CPU through ``harness.run_cell`` with the
    loader and window of ``program_spans`` in place, as its ``main`` runs
    a cell on the chip: the driving thread
    holds one dispatch span per worker and round, identified, and the
    window logs each round's phases inside its wall seconds."""
    monkeypatch.setattr(tr, "load_xplane", ps.load_xplane)
    # the CPU has no peaks; the readers that need them read v5e's here
    monkeypatch.setattr(harness, "peaks_for",
                        lambda kind: PEAKS["TPU v5 lite"])
    monkeypatch.setattr(harness, "window", ps.logged_window(harness.window))
    root = tiny_root(tmp_path)
    saved = tmp_path / "trace.json.gz"
    out = harness.run_cell(root, "tiny.dense", 2**31 + 7, 0.5, True,
                           t_start=0.0, require_tpu=False,
                           save_trace=str(saved))
    assert out["correct"] is True and out["failed"] == 0
    with gzip.open(saved, "rt") as f:
        trace = json.load(f)
    main = ps.main_thread(trace)
    dispatch = [h for h in trace["host"] if h[0] == "repro.dispatch"]
    assert dispatch and {h[3] for h in dispatch} == {main}
    rounds = sorted({h[4]["round"] for h in dispatch})
    assert len(dispatch) == 3 * len(rounds) == 3 * out["attempted"]
    assert sorted(h[4]["worker"] for h in dispatch) == sorted(
        [0, 1, 2] * len(rounds))
    (line,) = [x for x in capsys.readouterr().out.splitlines()
               if x.startswith("phases ")]
    logged = json.loads(line[len("phases "):])
    assert len(logged["phases"]) == out["attempted"]
    assert all(0 < c <= 1 for c in logged["main_over_wall"])
    assert logged["counters"]["rows_valid"] == 6 * out["attempted"]
    assert logged["counters"]["exchange_bytes"] == 0


DATA = tr.__file__.rsplit("/", 1)[0] + "/testdata"


def recorded(cell):
    with gzip.open(f"{DATA}/{cell}.spans.trace.json.gz", "rt") as f:
        return json.load(f)


@pytest.mark.parametrize("cell", ["phi3v-het3-dyn-4k", "yi9b-slices211-dyn-4k"])
def test_recorded_idle_is_put_down_to_the_programs_spans(cell):
    """On chip traces with the trainer's spans: the labels add up to the
    harness's idle time, and nine tenths of it or more falls under a
    ``repro.*`` span or the benchmark's feed, not under a bare round or
    outside the rounds."""
    trace = recorded(cell)
    idle = ps.idle_by_label(trace)
    s = tr.reduce(trace)
    assert sum(idle.values()) == pytest.approx(s.window_s - s.busy_s)
    unplaced = idle.get("bench.round", 0) + idle.get("outside", 0)
    assert unplaced < 0.1 * sum(idle.values())
    assert {h[0] for h in trace["host"]} >= {
        "repro.dispatch", "repro.await", "repro.combine", "repro.update",
        "repro.control"}
    assert ps.update_time_share(trace) > 0


def test_recorded_one_chip_stall_is_the_host_late_to_its_wait():
    """The one-chip excerpt holds a round whose second worker's wait
    outlasted its step by about 0.1 s: the chip's longest idle stretch
    lies under ``repro.await``, and the sequential fallback dispatched the
    next worker only when the wait returned."""
    trace = recorded("phi3v-het3-dyn-4k")
    (plane,) = ps.device_planes(trace)
    lo, hi = tr.covered(trace, [plane])
    busy = tr.union(tr.clip(trace["devices"][plane], lo, hi))
    a, b = max(tr.gaps(busy, lo, hi), key=lambda g: g[1] - g[0])
    assert b - a > 50e6
    waits = [h for h in trace["host"] if h[0] == "repro.await"
             and h[1] <= a and b <= h[1] + h[2] + 5e6]
    assert waits
    assert ps.slice_transfer_share(trace) is None


def test_recorded_four_chip_transfer_labels_idle():
    """On the 2+1+1 host the gradients' moves across slices leave every
    chip idle under ``repro.transfer``, a span of another thread than the
    driving one, one per round, each after the exchange of its
    round."""
    trace = recorded("yi9b-slices211-dyn-4k")
    assert len(ps.device_planes(trace)) == 4
    idle = ps.idle_by_label(trace)
    assert idle["repro.transfer"] > 0.02 * sum(idle.values())
    main = ps.main_thread(trace)
    transfers = [h for h in trace["host"] if h[0] == "repro.transfer"]
    exchanges = [h for h in trace["host"] if h[0] == "repro.exchange"]
    assert transfers and {h[3] for h in transfers} != {main}
    assert {h[3] for h in exchanges} == {main}
    rounds = [h[4]["round"] for h in transfers]
    assert len(rounds) == len(set(rounds)) >= 4
    assert set(rounds) <= {h[4]["round"] for h in exchanges}
    assert ps.slice_transfer_share(trace) > 0
