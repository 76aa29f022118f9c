"""Readings that set a cell's correctness limits, in one process on the
chip: the program's numbers on many seeds, the control's (the reference in
bfloat16 in the program's place) and each planted fault's on a few.

    python3 bench/control.py --workload <cell> --seeds 1,2,... \\
        --control-seeds 1,2,3 --fault-seeds 1,2,3 --out readings.jsonl

No window runs: the checked steps are the set-up's, as in a benchmark run.
Each line of ``--out`` is one reading: ``{"kind", "seed", "gaps"}``, with
kind ``sound``, ``control`` or a fault's name; the last lines of standard
output give the largest sound reading and the smallest of the others per
number.  Benchmark runs never run this.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def seeds(text: str) -> list[int]:
    return [int(s) for s in text.split(",") if s]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds, default=[])
    ap.add_argument("--control-seeds", type=seeds, default=[])
    ap.add_argument("--fault-seeds", type=seeds, default=[])
    ap.add_argument("--faults", default="")
    ap.add_argument("--out", required=True)
    ap.add_argument("--root", type=Path, default=ROOT,
                    help="the benchmark's root (tests use a copy)")
    ap.add_argument("--cpu", action="store_true",
                    help="run without a TPU (tests at a tiny size)")
    args = ap.parse_args(argv)

    from bench import faults, harness
    from bench.reference import Reference, compare

    names = [f for f in args.faults.split(",") if f] or sorted(faults.FAULTS)
    refs = {}

    def reading(kind, seed, plant=None):
        t = time.perf_counter()
        su = harness.set_up(args.root, args.workload, seed, plant=plant,
                            require_tpu=not args.cpu)
        spec, plan, prog = su.spec, su.plan, su.prog
        del su
        gc.collect()
        if plant is not None:
            plant.undo()
        key = (seed, json.dumps(plan))
        if key not in refs:
            refs[key] = Reference(spec, seed).run(plan)
        row = {"kind": kind, "seed": seed, "plan": plan,
               "gaps": compare(prog, refs[key]),
               "seconds": time.perf_counter() - t}
        if kind == "sound" and seed in args.control_seeds:
            control = Reference(spec, seed, dtype="bfloat16").run(plan)
            yield {"kind": "control", "seed": seed, "plan": plan,
                   "gaps": compare(control, refs[key])}
        yield row

    rows = []
    with open(args.out, "a") as out:
        jobs = [("sound", s, None) for s in args.seeds]
        jobs += [(f, s, f) for f in names for s in args.fault_seeds]
        for kind, seed, fault in jobs:
            plant = faults.plant(fault) if fault else None
            for row in reading(kind, seed, plant):
                rows.append(row)
                out.write(json.dumps(row) + "\n")
                out.flush()
                print("reading", json.dumps(row), flush=True)
    summary = {}
    for row in rows:
        for k, v in row["gaps"].items():
            s = summary.setdefault(row["kind"], {})
            pick = max if row["kind"] == "sound" else min
            s[k] = v if k not in s else pick(s[k], v)
    for kind, s in summary.items():
        print("summary", kind, json.dumps(s), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
