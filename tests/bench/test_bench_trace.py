"""The reduction from a profiler trace to busy, kernel, collective and idle
time: on hand-made events, and on traces recorded on TPU v5e chips
(``bench/testdata``): the busy/idle union against a timeline count, every
Pallas kernel's events kept by the stem of its name, the ops that move
data between chips told apart from the rest, and the readings of the
recorded traces pinned to what the reduction gave before it kept kernels
by stem."""

from __future__ import annotations

import gzip
import json

import numpy as np
import pytest

from _tiny import ROOT

from bench import trace_reduce as tr  # noqa: E402

DATA = ROOT / "bench" / "testdata"


def load(name):
    with gzip.open(DATA / name, "rt") as f:
        return json.load(f)


def test_union_gaps_and_labels_on_hand_made_events():
    events = [["a", 0, 10], ["b", 5, 10], ["c", 30, 5], ["d", 50, 100]]
    busy = tr.union(tr.clip(events, 2, 60))
    assert busy == [(2, 15), (30, 35), (50, 60)]
    assert tr.gaps(busy, 2, 60) == [(15, 30), (35, 50)]
    spans = [["bench.window", 0, 100], ["bench.round", 0, 40],
             ["bench.fetch", 20, 5]]
    assert tr.host_label(spans, 22) == "bench.fetch"
    assert tr.host_label(spans, 35) == "bench.round"
    assert tr.host_label(spans, 70) == "outside"
    trace = {"window": [2, 60], "spans": spans,
             "devices": {"/device:TPU:0": events + [["e", 200, 1]]}}
    s = tr.reduce(trace)
    assert s.busy_s == pytest.approx(28e-9)
    assert s.window_s == pytest.approx(58e-9)
    assert s.idle_s == {"bench.fetch": pytest.approx(15e-9),
                        "outside": pytest.approx(15e-9)}


def test_kernel_and_exchange_names():
    kernel = ('%attention.6 = (f32[7,4096,4096]{2,1,0:T(8,128)}) custom-call('
              's32[] %min.6), custom_call_target="tpu_custom_call"')
    assert tr.KERNEL.search(kernel)
    assert tr.op_name(kernel) == "attention.6"
    assert tr.kernel_stem(kernel) == "attention"
    # any Pallas call is a kernel, kept under its own stem
    other = ('%custom-call.3 = f32[8] custom-call(f32[8] %a), '
             'custom_call_target="tpu_custom_call"')
    assert tr.KERNEL.search(other) and tr.kernel_stem(other) == "custom-call"
    assert not tr.COLLECTIVE.search(other)
    for name in ("%fusion.12 = f32[2] fusion(f32[2] %a), kind=kLoop",
                 "%copy-start = (u32[2]{0:T(128)S(1)}) copy-start(%key.1)",
                 "%copy-done = u32[2]{0} copy-done(%copy-start)",
                 '%custom-call.4 = f32[8] custom-call(f32[8] %a), '
                 'custom_call_target="Sharding"'):
        assert not tr.KERNEL.search(name)
        assert not tr.COLLECTIVE.search(name)
    for name in ("%all-reduce.1 = f32[8] all-reduce(f32[8] %a)",
                 "%psum.113 = f32[4096,8000]{0,1:T(8,128)} all-reduce("
                 "f32[4096,8000]{0,1:T(8,128)} %fusion.57), channel_id=1",
                 "%all-gather-start = f32[8] all-gather-start(f32[4] %a)",
                 "%collective-permute.2 = f32[8] collective-permute(%a)"):
        assert tr.COLLECTIVE.search(name)
    # an operand named after a collective is not one
    assert not tr.COLLECTIVE.search(
        "%get-tuple-element.3 = f32[8] get-tuple-element(%all-reduce.4)")


def timeline_busy(events, lo, hi, step=1000):
    """Busy nanoseconds by counting covered instants on a 1 us grid."""
    grid = np.zeros((hi - lo) // step + 1, bool)
    for _, s, d in events:
        a = max(s, lo)
        b = min(s + d, hi)
        if b > a:
            grid[(a - lo + step - 1) // step:(b - lo + step - 1) // step] = 1
    return grid.sum() * step


def test_reduction_stops_where_a_chips_record_ends():
    trace = {"window": [0, 100], "spans": [],
             "devices": {"a": [["x", 0, 10], ["x", 80, 20]],
                         "b": [["x", 0, 10], ["x", 30, 10]]}}
    assert tr.covered(trace, ["a", "b"]) == (0, 40)
    s = tr.reduce(trace)
    assert s.window_s == pytest.approx(40e-9)
    assert s.busy_s == pytest.approx(15e-9)     # (10 + 20) / 2 chips


RECORDED = [("phi3v-het3-dyn-4k", 1), ("yi9b-slices211-dyn-4k", 4)]


@pytest.mark.parametrize("cell,chips", RECORDED)
def test_recorded_busy_is_the_union_of_op_intervals(cell, chips):
    """Busy time against a count of covered microseconds, and the idle
    time by host span adding up to the rest of the window."""
    trace = load(f"{cell}.trace.json.gz")
    planes = sorted(trace["devices"])
    lo, hi = tr.covered(trace, planes)
    s = tr.reduce(trace)
    assert s.chips == chips and s.window_s == pytest.approx((hi - lo) / 1e9)
    counted = [timeline_busy(trace["devices"][p], lo, hi) for p in planes]
    assert s.busy_s == pytest.approx(np.mean(counted) / 1e9, rel=2e-3)
    assert 0 < s.busy_s < s.window_s
    assert sum(s.idle_s.values()) == pytest.approx(s.window_s - s.busy_s)
    assert set(s.idle_s) <= {"bench.round", "bench.fetch", "bench.observe",
                             "outside"}


@pytest.mark.parametrize("cell,chips", RECORDED)
def test_recorded_kernel_events_are_the_flash_kernels(cell, chips):
    """Every Pallas call in these programs is a flash-attention kernel and
    is picked, under the stem ``attention``; the other custom calls and
    the fusions are not; each chip runs them."""
    trace = load(f"{cell}.trace.json.gz")
    for plane, events in trace["devices"].items():
        picked = [e for e in events if tr.KERNEL.search(e[0])]
        assert picked, plane
        for e in events:
            pallas = 'custom_call_target="tpu_custom_call"' in e[0]
            assert bool(tr.KERNEL.search(e[0])) == pallas, e[0][:200]
        assert {tr.kernel_stem(e[0]) for e in picked} == {"attention"}
    assert any("ConcatBitcast" in e[0] for v in trace["devices"].values()
               for e in v)
    s = tr.reduce(trace)
    assert set(s.kernel_s) == set(s.kernel_events) == {"attention"}


def test_recorded_exchange_is_the_two_chip_slices_all_reduce():
    """On the 2+1+1 host only worker 0's slice (chips 0 and 1) reduces
    across chips: its all-reduces are picked there, chips 2 and 3 have
    none, and the many copy-start/copy-done ops are not counted."""
    trace = load("yi9b-slices211-dyn-4k.trace.json.gz")
    s = tr.reduce(trace)
    assert s.collective_s[0] > 0 and s.collective_s[1] > 0
    assert s.collective_s[2] == s.collective_s[3] == 0
    for plane, events in trace["devices"].items():
        for e in events:
            if tr.COLLECTIVE.search(e[0]):
                assert " all-reduce(" in e[0]
                assert "replica_groups={{0,1}}" in e[0]
    copies = [e for v in trace["devices"].values() for e in v
              if " copy-start(" in e[0] or " copy-done(" in e[0]]
    assert copies and not any(tr.COLLECTIVE.search(e[0]) for e in copies)


def test_recorded_kernel_time_is_split_by_round():
    """Kernel seconds per ``bench.round`` span add up to the kernel time of
    the rounds the covered part holds whole; rounds cut by its edges are
    left out (None), so work and time are counted over the same rounds."""
    trace = load("yi9b-slices211-dyn-4k.trace.json.gz")
    s = tr.reduce(trace)
    whole = [k["attention"] for k in s.round_kernel_s if k is not None]
    assert whole and all(k > 0 for k in whole)
    assert sum(whole) <= s.kernel_s["attention"] + 1e-9
    # one round's kernels: the same work every round, within a few percent
    assert max(whole) / min(whole) < 1.05


# What the reduction gave for each recorded trace before it kept kernels by
# stem (the flash kernels' seconds over the covered window and per whole
# round, their events, and the two shares read from them): the same to
# the last digit.
PINNED = {
    "phi3v-het3-dyn-4k": (1.553236438, 22, [0.738990978, 0.738983825, None],
                          64.82760196970818, 4.162030319999999),
    "yi9b-slices211-dyn-4k": (
        3.747092285, 64,
        [0.709880816, 0.709867586, 0.709849806, 0.709884547, 0.709871699,
         None], 47.86816288355997, 21.72057461),
    "phi3v-het3-dyn-4k.spans": (
        1.499740784, 20, [0.738955765, 0.738985015, None],
        64.70416001117536, 7.286283680000006),
    "yi9b-slices211-dyn-4k.spans": (
        3.747177072, 64,
        [0.709881174, 0.709867365, 0.70988603, 0.709893352, 0.709897518,
         None], 47.11227912940015, 20.462835989999995),
}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_recorded_readings_are_pinned(name):
    from bench import harness

    kernel_s, events, rounds, time_share, idle_share = PINNED[name]
    s = tr.reduce(load(f"{name}.trace.json.gz"))
    assert s.kernel_s == {"attention": kernel_s}
    assert s.kernel_events == {"attention": events}
    assert s.round_kernel_s == [None if r is None else {"attention": r}
                                for r in rounds]
    run = harness.Run(conf={}, arch=None, traffic={}, chips=s.chips,
                      device_kind="TPU v5 lite", setup_s=1.0,
                      window_s=s.window_s, rounds=[], peak_bytes=0, trace=s)
    assert harness.load_reader(ROOT, "flash_time_share")(run) == time_share
    assert harness.load_reader(ROOT, "device_idle_share")(run) == idle_share
