"""The trainer's own spans in a device trace: idle time put down to what the
host was doing, the exposed cross-slice transfer and the optimizer update.

The trainer opens a profiler annotation ``repro.<name>`` around each part
of its round (``src/repro/train/spans.py``): ``dispatch``, ``await``,
``rerun``, ``exchange``, ``combine``, ``update`` and ``control`` on the
thread that drives it, and ``transfer`` on a thread of its own from the
cross-slice gradient puts until the moved gradients are ready.  Its update
is the program ``jit_update``.  ``load_xplane`` reads a trace into
``bench/trace_reduce.py``'s form with two more keys:

    {"window": ..., "devices": ..., "spans": ...,      (trace_reduce's)
     "host": [[name, start_ns, duration_ns, thread, stats], ...],
     "modules": {plane: [[program, start_ns, duration_ns], ...]}}

``host`` holds every ``bench.*`` and ``repro.*`` host span with the host
line (thread) it ran on and its stats (``round``, ``worker``); ``modules``
each device plane's "XLA Modules" line, the programs the chip ran.

``idle_by_label`` puts each stretch of device idle time down to one label:
``repro.transfer`` while a transfer span is open on any thread, else the
innermost span open on the thread that holds ``bench.window``, else
``outside``.  Three shares follow from it, each in %:

- ``host_idle_share``: idle under the driving thread's ``repro.*`` spans
  other than ``repro.await`` (the host's own work between launches) over
  the covered window (the round-dispatch layer);
- ``slice_transfer_share``: idle under ``repro.transfer`` over the covered
  window, only on more than one chip (the exchange across slices);
- ``update_time_share``: the device time of the update's ops (inside a
  ``jit_update`` program) over busy time (the optimizer update).

The harness reads none of these yet: its loader keeps only ``bench.*``
spans and its readers see only the reduced summary.  Run by hand,

    python3 bench/program_spans.py --workload <cell> --seed <n> --seconds 10 \\
        [--excerpt out.spans.trace.json.gz] [--save-trace whole.json.gz]

runs the cell once, traced, through ``harness.run_cell`` with this
module's ``load_xplane`` in the place of ``trace_reduce.load_xplane`` and
the window followed by a ``phases`` log line (each round's
``StepRecord.phases`` beside its wall seconds, and the window's deltas of
the trainer's counters), for this process only.  Its last line is the run's
result with ``idle_by_label`` and the three shares added.
"""

from __future__ import annotations

import argparse
import gzip
import json
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import trace_reduce as tr  # noqa: E402

PROGRAM_PREFIX = "repro."
TRANSFER = "repro.transfer"
AWAIT = "repro.await"
UPDATE_PROGRAM = "jit_update"
MODULE_LINES = ("XLA Modules",)
HOST_PREFIXES = (tr.SPAN_PREFIX, PROGRAM_PREFIX)


def load_xplane(path: str) -> dict:
    """``trace_reduce.load_xplane``'s dict plus ``host`` and ``modules``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, modules, host = {}, {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            ops, mods = [], []
            for line in plane.lines:
                events = [[e.name, int(e.start_ns), int(e.duration_ns)]
                          for e in line.events]
                if line.name in tr.OP_LINES:
                    ops += events
                elif line.name in MODULE_LINES:
                    mods += events
            devices[plane.name] = sorted(ops, key=lambda x: x[1])
            modules[plane.name] = sorted(mods, key=lambda x: x[1])
        elif plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                thread = f"{plane.name}/{line.name}/{i}"
                host += [[e.name, int(e.start_ns), int(e.duration_ns), thread,
                          {k: v for k, v in e.stats}]
                         for e in line.events
                         if e.name.startswith(HOST_PREFIXES)]
    host.sort(key=lambda x: x[1])
    windows = [h for h in host if h[0] == tr.WINDOW]
    if len(windows) != 1:
        raise ValueError(f"{len(windows)} {tr.WINDOW} spans in the trace")
    _, start, dur, _, _ = windows[0]
    return {"window": [start, start + dur], "devices": devices,
            "spans": [h[:3] for h in host
                      if h[0].startswith(tr.SPAN_PREFIX)],
            "host": host, "modules": modules}


def excerpt(trace: dict, seconds: float) -> dict:
    """``trace_reduce.excerpt`` with the host spans and programs kept."""
    out = tr.excerpt(trace, seconds)
    lo, hi = out["window"]

    def keep(evs):
        return [e for e in evs if e[1] < hi and e[1] + e[2] > lo]

    main = main_thread(trace)
    out["host"] = [h for h in keep(trace["host"]) if h[0] != tr.WINDOW] + [
        [tr.WINDOW, lo, hi - lo, main, {}]]
    out["modules"] = {k: keep(v) for k, v in trace["modules"].items()}
    return out


# ----------------------------------------------------------------- labels


def main_thread(trace: dict) -> str:
    """The host line that holds ``bench.window``: the thread driving the
    trainer's rounds."""
    (thread,) = {h[3] for h in trace["host"] if h[0] == tr.WINDOW}
    return thread


def label_segments(trace: dict, lo: int, hi: int) -> list:
    """[start, end, label] covering [lo, hi]: the label an idle instant
    there takes (module docstring)."""
    main = main_thread(trace)
    spans = [(s, s + d, name) for name, s, d, thread, _ in trace["host"]
             if name == TRANSFER or (thread == main and name != tr.WINDOW)]
    spans = [(max(a, lo), min(b, hi), n) for a, b, n in spans
             if b > lo and a < hi]
    edges = sorted({lo, hi} | {a for a, _, _ in spans}
                   | {b for _, b, _ in spans})
    out = []
    for a, b in zip(edges, edges[1:]):
        t = (a + b) // 2
        open_ = [(y - x, n) for x, y, n in spans if x <= t < y]
        if any(n == TRANSFER for _, n in open_):
            label = TRANSFER
        elif open_:
            label = min(open_)[1]
        else:
            label = "outside"
        if out and out[-1][2] == label and out[-1][1] == a:
            out[-1][1] = b
        else:
            out.append([a, b, label])
    return out


def idle_by_label(trace: dict, planes=None) -> dict:
    """Idle seconds by label over the covered part of the window, mean over
    the chips; they add up to the window minus busy time."""
    names = device_planes(trace, planes)
    lo, hi = tr.covered(trace, names)
    segments = label_segments(trace, lo, hi)
    idle = {}
    for name in names:
        busy = tr.union(tr.clip(trace["devices"][name], lo, hi))
        j = 0
        for a, b in tr.gaps(busy, lo, hi):
            while segments[j][1] <= a:
                j += 1
            k = j
            while k < len(segments) and segments[k][0] < b:
                x, y, label = segments[k]
                part = min(b, y) - max(a, x)
                if part > 0:
                    idle[label] = idle.get(label, 0.0) + part
                k += 1
    n = max(len(names), 1)
    return {k: v / n / 1e9 for k, v in idle.items()}


def device_planes(trace: dict, planes=None) -> list:
    return sorted(k for k, v in trace["devices"].items() if v) \
        if planes is None else list(planes)


def window_s(trace: dict, planes=None) -> float:
    lo, hi = tr.covered(trace, device_planes(trace, planes))
    return (hi - lo) / 1e9


def has_program_spans(trace: dict) -> bool:
    return any(h[0].startswith(PROGRAM_PREFIX) for h in trace["host"])


# ----------------------------------------------------------------- shares


def host_idle_share(trace: dict, planes=None):
    """Idle under the driving thread's own ``repro.*`` work (not its wait
    for the workers, not a transfer) over the covered window, in %; None
    where the program opened no spans."""
    if not has_program_spans(trace):
        return None
    idle = idle_by_label(trace, planes)
    host = sum(v for k, v in idle.items() if k.startswith(PROGRAM_PREFIX)
               and k not in (AWAIT, TRANSFER))
    return 100.0 * host / window_s(trace, planes)


def slice_transfer_share(trace: dict, planes=None):
    """Idle under ``repro.transfer`` over the covered window, in %; None on
    one chip, or where the program opened no spans."""
    names = device_planes(trace, planes)
    if len(names) < 2 or not has_program_spans(trace):
        return None
    idle = idle_by_label(trace, names)
    return 100.0 * idle.get(TRANSFER, 0.0) / window_s(trace, names)


def update_time_share(trace: dict, planes=None):
    """Device time of the ops inside a ``jit_update`` program over busy
    time, summed over the chips, in %; None where no such program ran in
    the covered window."""
    names = device_planes(trace, planes)
    lo, hi = tr.covered(trace, names)
    update = busy = 0
    for name in names:
        ops = trace["devices"][name]
        busy += sum(b - a for a, b in tr.union(tr.clip(ops, lo, hi)))
        programs = tr.union(tr.clip(
            [m for m in trace["modules"].get(name, [])
             if m[0].startswith(UPDATE_PROGRAM)], lo, hi))
        update += sum(b - a for a, b in tr.union(
            (max(a, x), min(b, y)) for a, b in tr.clip(ops, lo, hi)
            for x, y in programs if a < y and x < b))
    if not update:
        return None
    return 100.0 * update / busy


SHARES = {"host_idle_share": host_idle_share,
          "slice_transfer_share": slice_transfer_share,
          "update_time_share": update_time_share}


# ------------------------------------------------------------ by hand


COUNTERS = ("compiles", "compile_s", "accum_traces", "exchange_bytes",
            "rows_valid", "rows_padded")


def logged_window(window):
    """``harness.window`` followed by a ``phases`` log line."""
    import jax

    from bench import harness

    def logged(session, traffic, seconds, spans):
        trainer = session.trainer
        before = {c: getattr(trainer, c, None) for c in COUNTERS}
        rounds, window_s_, failed = window(session, traffic, seconds, spans)
        records = trainer.history[-len(rounds):] if rounds else []
        jax.block_until_ready(trainer.params)
        deadline = time.monotonic() + 5.0     # transfers join on a thread
        while (trainer.concurrent and time.monotonic() < deadline and
               any("transfer" not in (r.phases or {}) for r in records)):
            time.sleep(0.01)
        phases = [dict(r.phases or {}) for r in records]
        main = [sum(v for k, v in p.items() if k != "transfer")
                for p in phases]
        covered = [m / r.seconds for m, r in zip(main, rounds)]
        slowest = max(range(len(rounds)), key=lambda i: rounds[i].seconds,
                      default=None)
        harness.log(
            "phases", round_s=[r.seconds for r in rounds], phases=phases,
            main_over_wall=covered,
            slowest={"round_s": rounds[slowest].seconds,
                     "phases": phases[slowest]} if rounds else None,
            counters={c: (getattr(trainer, c) - v if v is not None else None)
                      for c, v in before.items()})
        return rounds, window_s_, failed
    return logged


def main(argv=None) -> int:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--excerpt", default=None,
                    help="write the first --excerpt-seconds of the window "
                         "here (.json.gz)")
    ap.add_argument("--excerpt-seconds", type=float, default=2.5)
    ap.add_argument("--save-trace", default=None,
                    help="also keep the window's whole trace here (.json.gz)")
    args = ap.parse_args(argv)

    from bench import harness

    tr.load_xplane = load_xplane
    harness.window = logged_window(harness.window)
    with tempfile.TemporaryDirectory() as tmp:
        saved = args.save_trace or str(Path(tmp) / "trace.json.gz")
        try:
            out = harness.run_cell(ROOT, args.workload, args.seed,
                                   args.seconds, True, t_start=t_start,
                                   save_trace=saved)
        except harness.NoChip as e:
            print(f"bench: {e}", file=sys.stderr)
            return 2
        with gzip.open(saved, "rt") as g:
            trace = json.load(g)
    out["idle_by_label"] = idle_by_label(trace)
    for name, share in SHARES.items():
        value = share(trace)
        if value is not None:
            out["metrics"][name] = {"value": value, "unit": "%"}
    if args.excerpt:
        with gzip.open(args.excerpt, "wt") as g:
            json.dump(excerpt(trace, args.excerpt_seconds), g)
    harness.print_checks(out["checks"])
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
