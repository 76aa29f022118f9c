"""From a profiler trace to the numbers the per-layer metrics read.

``load_xplane`` reads the ``.xplane.pb`` the JAX profiler writes into a
plain dict (what ``bench/testdata`` holds):

    {"window": [start_ns, end_ns],
     "devices": {plane: [[op, start_ns, duration_ns], ...]},
     "spans": [[name, start_ns, duration_ns], ...]}

``devices`` holds each accelerator plane's op events (its "XLA Ops"
line); ``spans`` the benchmark's own host spans (names ``bench.*``), on
the same clock; ``window`` is the span ``bench.window``.  ``reduce``
turns that into busy time, each Pallas kernel's time by name (over the
window and per round), collective time, the ops that took most time and
the idle gaps by what the host was doing.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

# An op event is named by its HLO instruction text,
#   %attention.6 = (f32[...], ...) custom-call(...), custom_call_target=...
# Every Pallas kernel is such a custom call; its time is kept under the
# stem of its op name (``kernel_stem``): the flash-attention kernels
# (forward, and the backward's dq and dk/dv) are the calls of the jitted
# ``attention`` wrapper, stem ``attention``.
KERNEL = re.compile(r'^%?[\w.-]+ = .*custom_call_target="tpu_custom_call"')
# The collectives, by their HLO opcode (an all-reduce inside a shard_map
# is named after its ``psum``): the exchange inside one program's chips.
# copy-start and copy-done move data between memories of one chip.
# Transfers that ``jax.device_put`` makes between chips (the gradients
# moved across slices) are no XLA op, are not on the op line read here,
# and count as idle.
COLLECTIVE = re.compile(r" (all-reduce|all-gather|reduce-scatter|"
                      r"collective-permute|all-to-all|send|recv)"
                      r"(-start|-done)?\(")
SPAN_PREFIX = "bench."
WINDOW = "bench.window"
ROUND = "bench.round"
OP_LINES = ("XLA Ops",)


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"{len(paths)} xplane files under {log_dir}")
    return paths[0]


def load_xplane(path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            ops = []
            for line in plane.lines:
                if line.name in OP_LINES:
                    ops += [[e.name, int(e.start_ns), int(e.duration_ns)]
                            for e in line.events]
            devices[plane.name] = sorted(ops, key=lambda x: x[1])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [[e.name, int(e.start_ns), int(e.duration_ns)]
                          for e in line.events
                          if e.name.startswith(SPAN_PREFIX)]
    windows = [s for s in spans if s[0] == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"{len(windows)} {WINDOW} spans in the trace")
    _, start, dur = windows[0]
    return {"window": [start, start + dur], "devices": devices,
            "spans": sorted(spans, key=lambda x: x[1])}


# ----------------------------------------------------------------- reduction


def clip(events, lo: int, hi: int):
    """(start, end) of each event, clipped to [lo, hi]; empty ones gone."""
    out = []
    for _, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((a, b))
    return out


def union(intervals) -> list[tuple[int, int]]:
    """Merged, sorted intervals."""
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], b))
        else:
            merged.append((a, b))
    return merged


def gaps(busy, lo: int, hi: int) -> list[tuple[int, int]]:
    """The stretches of [lo, hi] that ``busy`` (merged) leaves idle."""
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def host_label(spans, t: int) -> str:
    """The innermost benchmark span open at ``t`` (not the window)."""
    best = None
    for name, s, d in spans:
        if name != WINDOW and s <= t < s + d and (best is None
                                                  or d < best[1]):
            best = (name, d)
    return best[0] if best else "outside"


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    chips: int
    busy_s: float            # mean over the chips
    kernel_s: dict           # kernel stem -> seconds, summed over the chips
    kernel_events: dict      # kernel stem -> events
    collective_s: list[float]  # per chip
    op_s: dict               # op name -> seconds, mean over the chips
    idle_s: dict             # host span -> idle seconds, mean over chips
    # per ``bench.round`` span of the window, in order: {kernel stem:
    # seconds inside it summed over the chips}, or None where the span is
    # not wholly inside the covered part
    round_kernel_s: list = dataclasses.field(default_factory=list)

    def busy_total_s(self) -> float:
        return self.busy_s * self.chips


def covered(trace: dict, planes) -> tuple[int, int]:
    """The part of the window that every plane's events cover.  The
    profiler can stop recording a chip's ops before the window closes
    (seen on a four-chip host: one chip's events ended 3.2 s into a 10 s
    window while the others ran on), so the reduction ends where the
    first plane's record ends."""
    lo, hi = trace["window"]
    for name in planes:
        events = trace["devices"][name]
        if events:
            hi = min(hi, max(s + d for _, s, d in events))
    return lo, hi


def reduce(trace: dict, planes=None) -> TraceSummary:
    """Busy, kernel, collective and idle time inside the part of the window
    that the device planes named in ``planes`` (all with events where
    None) all cover."""
    names = sorted(k for k, v in trace["devices"].items() if v) \
        if planes is None else list(planes)
    lo, hi = covered(trace, names)
    busy_total = 0.0
    kernel, kernel_events = {}, {}
    collective, op_s, idle_s = [], {}, {}
    for name in names:
        events = trace["devices"][name]
        busy = union(clip(events, lo, hi))
        busy_total += sum(b - a for a, b in busy)
        coll = 0
        for op, s, d in events:
            inside = sum(b - a for a, b in clip([(op, s, d)], lo, hi))
            if not inside:
                continue
            name = op_name(op)
            op_s[name] = op_s.get(name, 0.0) + inside
            if KERNEL.search(op):
                stem = kernel_stem(op)
                kernel[stem] = kernel.get(stem, 0) + inside
                kernel_events[stem] = kernel_events.get(stem, 0) + 1
            if COLLECTIVE.search(op):
                coll += inside
        collective.append(coll / 1e9)
        for a, b in gaps(busy, lo, hi):
            label = host_label(trace["spans"], (a + b) // 2)
            idle_s[label] = idle_s.get(label, 0.0) + (b - a)
    rounds = [(s_, s_ + d) for name, s_, d in trace["spans"]
              if name == ROUND and trace["window"][0] <= s_]
    kernels = [(kernel_stem(e[0]), e) for name in names
               for e in trace["devices"][name] if KERNEL.search(e[0])]
    round_kernel_s = []
    for a, b in rounds:
        if not (lo <= a and b <= hi):
            round_kernel_s.append(None)
            continue
        ns = {}
        for stem, e in kernels:
            ns[stem] = ns.get(stem, 0) + sum(y - x for x, y in clip([e], a, b))
        round_kernel_s.append({k: v / 1e9 for k, v in ns.items()})
    n = max(len(names), 1)
    return TraceSummary(
        window_s=(hi - lo) / 1e9, chips=len(names),
        busy_s=busy_total / n / 1e9,
        kernel_s={k: v / 1e9 for k, v in kernel.items()},
        kernel_events=kernel_events, collective_s=collective,
        op_s={k: v / n / 1e9 for k, v in op_s.items()},
        idle_s={k: v / n / 1e9 for k, v in idle_s.items()},
        round_kernel_s=round_kernel_s)


def op_name(event: str) -> str:
    """``attention.6`` of ``%attention.6 = (f32[...]) custom-call(...)``."""
    return event.split(" = ", 1)[0].lstrip("%")


def kernel_stem(event: str) -> str:
    """``attention`` of ``%attention.6 = ...``: the op name without the
    number XLA appends to each instance."""
    return op_name(event).split(".", 1)[0]


def breakdown(summary: TraceSummary, top: int = 10) -> dict:
    """The ops that took most device time and the idle time by what the
    host was doing, seconds per chip, each list at most ``top`` long."""
    def largest(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])
                ][:top]
    return {"device_ops": largest(summary.op_s),
            "idle_gaps": largest(summary.idle_s)}


def excerpt(trace: dict, seconds: float) -> dict:
    """The first ``seconds`` of the window, for a small recorded file."""
    lo, hi = trace["window"]
    hi = min(hi, lo + int(seconds * 1e9))
    keep = lambda evs: [e for e in evs if e[1] < hi and e[1] + e[2] > lo]
    return {"window": [lo, hi],
            "devices": {k: keep(v) for k, v in trace["devices"].items()},
            "spans": [s for s in keep(trace["spans"]) if s[0] != WINDOW]
            + [[WINDOW, lo, hi - lo]]}
