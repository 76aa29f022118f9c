"""One run of one benchmark cell.

Everything a cell is made of is found by name from ``BENCHMARK.json``:
the cell's configuration (``bench/configs/<config>.json``), the
architecture module its ``"model"`` key names (``bench/arch/<model>.py``:
weights, reference forward pass, work counts and the mapping to the
program's ModelConfig), its traffic (``bench/traffic/<traffic>.json``),
the limits of its correctness check (``bench/limits/<cell>.json``) and one
reader per metric (``bench/metrics/<metric>.py``, a ``read(run)`` that
returns the value or ``None`` where the run has nothing to read).  Adding
an architecture, a configuration, a traffic mix or a metric adds files;
no file here changes.

A run: set-up (weights from the seed on the device, the trainer built
through ``repro.api`` with its probe round, every bucket the workers can
reach compiled and run once, then the first checked steps driven through
``Session.step`` on the path the window takes), then the measured window
of ``Session.step`` calls, then the check of the checked steps against
the plain reference once the program's state is freed.  What the
benchmark asks of the trainer beyond ``repro.api`` goes through
``bench/program.py``.  With ``trace`` the window runs under the profiler and the result
carries the per-layer metrics; without it, the end-to-end metrics.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import gc
import gzip
import importlib.util
import json
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Optional

import jax
import jax.numpy as jnp

from bench import program, trace_reduce
from bench.inputs import Feed, make_params
from bench.peaks import peaks_for
from bench.reference import Reference, compare, leaf_norms

ROOT = Path(__file__).resolve().parents[1]
SPAN = "bench."


class NoChip(RuntimeError):
    """The machine does not hold the chips the cell asks for."""


# ------------------------------------------------------------- the spec


def read_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(root: Path, name: str) -> dict:
    """The cell ``name`` of ``root/BENCHMARK.json`` with its files read."""
    spec = read_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    entry = configs[cell["config"]]
    conf = read_json(root / entry["file"])

    def applies(m):
        return "workloads" not in m or name in m["workloads"]

    return {
        "cell": cell,
        "config": conf,
        "arch": load_arch(root, conf["model"]),
        "traffic": read_json(root / "bench" / "traffic"
                             / f"{cell['traffic']}.json"),
        "limits": read_json(root / "bench" / "limits" / f"{name}.json"),
        "end_to_end": [m for m in spec["end_to_end"] if applies(m)],
        "per_layer": [m for m in spec["per_layer"] if applies(m)],
    }


def _load_module(path: Path, prefix: str):
    name = path.stem.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(f"{prefix}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(root: Path, metric: str):
    """``read`` of ``bench/metrics/<metric>.py``."""
    return _load_module(root / "bench" / "metrics" / f"{metric}.py",
                        "bench_metric").read


@functools.lru_cache(maxsize=None)
def load_arch(root: Path, model: str):
    """The architecture module ``bench/arch/<model>.py``, loaded once per
    path, so that what is cached by module (the jitted weight maker) is
    found again by every run of a process."""
    return _load_module(root / "bench" / "arch" / f"{model}.py", "bench_arch")


# ------------------------------------------------------------ measuring


class CompileMeter:
    """Backend compiles (persistent-cache loads included) from JAX's
    monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event, duration, **_):
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration


class Spans:
    """Host seconds inside the benchmark's calls into the program, and the
    same calls as profiler annotations (names ``bench.*``)."""

    def __init__(self):
        self.seconds: dict[str, float] = {}

    @contextlib.contextmanager
    def span(self, name: str):
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation(SPAN + name):
            yield
        self.seconds[name] = self.seconds.get(name, 0.0) + (
            time.perf_counter() - t)

    def wrap(self, name: str, fn):
        def spanned(*a, **kw):
            with self.span(name):
                return fn(*a, **kw)
        return spanned


@dataclasses.dataclass
class Round:
    batches: list          # valid rows per worker
    buckets: list          # rows computed per worker (bucket)
    worker_times: list     # the trainer's measured seconds per worker
    loss: float
    seconds: float


@dataclasses.dataclass
class Run:
    """What the metric readers read."""

    conf: dict
    arch: object           # the configuration's bench/arch module
    traffic: dict
    chips: int
    device_kind: str
    setup_s: float
    window_s: float
    rounds: list
    peak_bytes: int
    trace: Optional[trace_reduce.TraceSummary] = None

    @property
    def seq_len(self) -> int:
        return self.traffic["seq_len"]

    @property
    def tokens(self) -> int:
        return sum(sum(r.batches) for r in self.rounds) * self.seq_len

    @property
    def peaks(self):
        return peaks_for(self.device_kind)


# ------------------------------------------------------------ the program


def reachable_batches(traffic: dict) -> range:
    """Every batch a worker can be given: the controller's bounds, within
    what the global batch leaves after the others' least."""
    k, total = traffic["workers"], traffic["workers"] * traffic["b0"]
    lo = traffic["controller"].get("b_min", 1)
    hi = traffic["controller"].get("b_max") or total
    return range(lo, min(hi, total - (k - 1) * lo) + 1)


def warm_buckets(trainer, feed: Feed, traffic: dict) -> int:
    """Every bucket the workers' batches can reach run once, on rows the
    feed does not log, so that no trace or compile falls into the checked
    steps or the window.  Returns how many ran."""
    def rows(worker, n):
        feed.warm(n)
        return feed.rows(worker, 0, n)
    return program.warm(trainer, rows, reachable_batches(traffic))


def peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


# ------------------------------------------------------------------ a run


def log(tag: str, **fields) -> None:
    print(f"{tag} {json.dumps(fields, default=str)}", flush=True)


def check_chips(chips: int, require_tpu: bool) -> list:
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform "
                     f"{devices[0].platform!r}); nothing runs on the CPU")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX has "
                     f"{len(devices)}")
    return devices[:chips]


def checked_steps(session, feed: Feed, arch, conf: dict, seed: int, n: int,
                  b1: float) -> tuple[dict, list]:
    """The first ``n`` steps, through ``Session.step`` on the rows the
    feed logs: each step's loss, the first combined gradient's leaf norms
    (Adam's first moment after one step over 1 - b1) and the leaf norms of
    the parameters' change over the ``n`` steps."""
    trainer = session.trainer
    losses, plan, grad_norms = [], [], None
    for i in range(n):
        pre = list(session.batches)
        start = len(feed.log)
        rec = session.step()
        plan.append([(w, c, pre[w]) for w, c, _ in feed.log[start:]])
        losses.append(float(rec.loss))
        if i == 0:
            grad_norms = {k: v / (1 - b1) for k, v in
                          leaf_norms(program.first_moment(trainer)).items()}
    p0 = jax.device_put(make_params(arch, conf, seed),
                        jax.tree.map(lambda a: a.sharding, trainer.params))
    delta = leaf_norms(jax.tree.map(jnp.subtract, trainer.params, p0))
    del p0
    return {"losses": losses, "grad_norms": grad_norms,
            "delta_norms": delta}, plan


def window(session, traffic: dict, seconds: float, spans: Spans):
    """``Session.step`` until ``seconds`` have passed; the window closes
    with the round in flight.  Returns the rounds, the window's seconds and
    how many rounds failed."""
    trainer = session.trainer
    rounds, failed = [], 0
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(SPAN + "window"):
        while True:
            pre = list(session.batches)
            buckets = [trainer.bucket_for(k, b) for k, b in enumerate(pre)]
            a = time.perf_counter()
            try:
                with spans.span("round"):
                    rec = session.step()
            except Exception as e:  # a failed round ends the window
                log("round_failed", error=repr(e))
                failed += 1
                break
            b = time.perf_counter()
            if not math.isfinite(rec.loss):
                failed += 1
            rounds.append(Round(pre, buckets, list(rec.worker_times or []),
                                float(rec.loss), b - a))
            if b - t0 >= seconds:
                break
    return rounds, time.perf_counter() - t0, failed


@dataclasses.dataclass
class SetUp:
    spec: dict
    devices: list
    feed: Feed
    spans: Spans
    meter: CompileMeter
    session: object
    prog: dict
    plan: list
    checked_traces: int     # traces the checked steps made: 0 when warm


def set_up(root: Path, name: str, seed: int, *, require_tpu: bool = True,
           plant=None) -> SetUp:
    """Everything before the window: the trainer built, every bucket its
    workers can reach run once, and the trainer driven through the
    checked steps, which then take the path the window's rounds take (no
    fresh trace, no timing re-run).  Off the chip (tests) the persistent
    compile cache stays off."""
    spec = load_cell(root, name)
    cell, conf, traffic = spec["cell"], spec["config"], spec["traffic"]
    arch = spec["arch"]
    devices = check_chips(cell["chips"], require_tpu)
    cache = None
    if require_tpu:
        from repro.launch.compile_cache import enable_compile_cache

        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        cache = enable_compile_cache()
    log("setup", compile_cache=cache, cell=name, seed=seed,
        device_kind=devices[0].device_kind, chips=len(devices))
    meter = CompileMeter()
    spans = Spans()
    feed = Feed(conf, traffic["seq_len"], seed)
    session = program.build_session(
        arch.program_config(conf), traffic, seed,
        make_params(arch, conf, seed),
        spans.wrap("fetch", feed.next_batch),
        observe=lambda fn: spans.wrap("observe", fn), plant=plant)
    trainer = session.trainer
    warmed = warm_buckets(trainer, feed, traffic)
    traces0 = program.traces(trainer)
    prog, plan = checked_steps(session, feed, arch, conf, seed,
                               traffic["checked_steps"],
                               traffic["optimizer"]["b1"])
    traces = program.traces(trainer) - traces0
    log("checked", losses=prog["losses"], plan=plan, warmed_buckets=warmed,
        traces=traces)
    return SetUp(spec, devices, feed, spans, meter, session, prog, plan,
                 traces)


def run_cell(root: Path, name: str, seed: int, seconds: float,
             trace: bool, *, t_start: float, require_tpu: bool = True,
             plant=None, save_trace: Optional[str] = None) -> dict:
    """One run of the cell; returns the result line's object.
    ``save_trace``: a path to write the traced window's events to (gzipped
    JSON, ``trace_reduce``'s form), for looking at by hand."""
    su = set_up(root, name, seed, require_tpu=require_tpu, plant=plant)
    spec, devices, spans, meter = su.spec, su.devices, su.spans, su.meter
    cell, conf, traffic = spec["cell"], spec["config"], spec["traffic"]
    session, trainer = su.session, su.session.trainer
    log("setup_done", compiles=meter.count, compile_s=meter.seconds)

    tmp = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(tmp, profiler_options=opts)
    spans.seconds.clear()
    compiles0, traces0 = meter.count, program.traces(trainer)
    # the objects set-up left (traces, lowered and compiled programs) are
    # kept out of the collector's scans during the window, where a full
    # collection over them stalled rounds in some runs and not others
    gc.collect()
    gc.freeze()
    collections0 = [g["collections"] for g in gc.get_stats()]
    setup_s = time.perf_counter() - t_start
    rounds, window_s, failed = window(session, traffic, seconds, spans)
    in_window = {"compiles": meter.count - compiles0,
                 "traces": program.traces(trainer) - traces0}
    collections = [g["collections"] - c for g, c in
                   zip(gc.get_stats(), collections0)]
    gc.unfreeze()
    if trace:
        jax.profiler.stop_trace()
    log("window", rounds=len(rounds), seconds=window_s, **in_window,
        gc_collections=collections,
        round_s=[r.seconds for r in rounds],
        batches=[r.batches for r in rounds[-3:]],
        worker_times=[r.worker_times for r in rounds[-3:]])
    used = program.devices(trainer)
    peak = peak_bytes(used)
    log("memory", **max((d.memory_stats() or {} for d in used),
                        key=lambda m: m.get("peak_bytes_in_use", 0)))

    run = Run(conf=conf, arch=spec["arch"], traffic=traffic,
              chips=cell["chips"], device_kind=devices[0].device_kind,
              setup_s=setup_s, window_s=window_s, rounds=rounds,
              peak_bytes=peak)
    prog, plan = su.prog, su.plan
    del su, session, trainer
    gc.collect()          # the trainer's jitted closures hold it in a cycle

    summary = None
    if trace:
        raw = trace_reduce.load_xplane(trace_reduce.find_xplane(tmp))
        shutil.rmtree(tmp, ignore_errors=True)
        planes = [p for p in raw["devices"]
                  if any(p.endswith(f":{d.id}") for d in used)]
        summary = trace_reduce.reduce(raw, planes)
        run.trace = summary
        lo = raw["window"][0]
        log("trace", window_s=(raw["window"][1] - lo) / 1e9,
            covered_s=summary.window_s, planes={
                p: max((s + d for _, s, d in raw["devices"][p]),
                       default=lo) / 1e9 - lo / 1e9 for p in planes})
        if save_trace:
            with gzip.open(save_trace, "wt") as f:
                json.dump(raw, f)
        del raw

    checks = check(spec, seed, plan, prog)
    correct = failed == 0 and all(c["value"] <= c["limit"]
                                  for c in checks.values())
    wanted = spec["per_layer"] if trace else spec["end_to_end"]
    metrics = {}
    for m in wanted:
        value = load_reader(root, m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    out = {"correct": correct, "attempted": len(rounds), "failed": failed,
           "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        out["breakdown"] = trace_reduce.breakdown(summary)
    out["window_compiles"] = in_window
    out["checks"] = checks
    return out


def reference_gaps(spec: dict, seed: int, plan: list, prog: dict) -> dict:
    """The numbers ``compare`` gives for the checked steps against the
    plain reference."""
    t = time.perf_counter()
    ref = Reference(spec, seed).run(plan)
    log("reference", seconds=time.perf_counter() - t,
        losses=ref["losses"], program_losses=prog["losses"])
    return compare(prog, ref)


def check(spec: dict, seed: int, plan: list, prog: dict) -> dict:
    """Each number of the check beside its limit from
    ``bench/limits/<cell>.json``."""
    limits = spec["limits"]["limits"]
    return {k: {"value": v, "limit": limits[k]}
            for k, v in reference_gaps(spec, seed, plan, prog).items()}


def print_checks(checks: dict) -> None:
    for k, c in checks.items():
        print(f"check {k} {c['value']:.6g} limit {c['limit']:.6g}",
              file=sys.stderr)
