"""Ragged SPMD execution on a real JAX device mesh (DESIGN.md §11-§12).

`HeterogeneousTrainer` closes the dynamic-batching loop against the cluster
*simulator*: real SGD, modelled wall-clock.  This module closes it against
real hardware: K logical workers run on an actual ``jax`` mesh with *ragged*
per-worker batch sizes, and the controller observes **measured** step times
(device-synced wall clock, EWMA-filtered) instead of simulated ones.

Execution model (DESIGN.md §12):

  * each worker owns a **disjoint, contiguous slice** of the mesh data axis
    (`core.placement.SlicePlan` — disjoint / exhaustive / quantum-aligned
    by construction), so the K bucketed gradient calls dispatch
    **concurrently**: JAX async dispatch is left unblocked while all K
    calls are in flight, and per-slice completion timestamps are collected
    by awaiter threads blocking on each slice's outputs — a BSP round costs
    max-of-workers wall time, not sum-of-workers;
  * worker k's mini-batch b_k is padded up to a *bucketed* shape
    ``bucket_up(b_k)`` (geometric ladder, ``core.batching``, anchored at
    the worker's slice extent so every padded batch shards evenly); slots
    past b_k carry zero weight via the same validity masks the simulator
    path uses for remainder microbatches;
  * each slice computes the masked gradient sum of its rows and
    :func:`repro.core.grad.weighted_psum` divides the per-slice gradient
    sum by the mask-weight sum ONCE — padding rows contribute exactly zero
    and the SUM-gradient contract (DESIGN.md §4) is preserved bit-for-bit
    relative to an unpadded computation; per-worker gradients are then
    combined with the paper's lambda weights
    (:func:`repro.core.grad.combine_weighted`), identical to the sim path;
  * each worker's dispatch→completion interval is measured; dispatches that
    triggered a fresh XLA trace are re-executed once solo so compile time
    never pollutes the control signal; an EWMA filter (``time_alpha``)
    smooths scheduler jitter before the controller's own filtering.

The measured completions feed a :class:`_MeasuredTimeModel` that duck-types
the ``ClusterSim`` surface :class:`repro.train.engine.EventEngine` drives,
so **BSP, ASP and elastic schedules** all run through the same event queue
as the sim backend — ASP pops the predicted-earliest completion (per-worker
EWMA rates from real measurements), executes that worker's gradient on the
params it last read, and updates the rate model with the new measurement.

When the data axis has fewer devices than workers (e.g. the single-device
test container) the trainer falls back to time-multiplexing all workers
over the full axis — the PR-3 behavior; everything but the concurrency
(ASP, checkpointing, membership) works identically there.

Checkpointing: :meth:`exec_state_dict` / :meth:`load_exec_state_dict`
capture the measurement/EWMA state, the rate model + clock, the bucket
ladders visited, and the slice assignment, so
:meth:`repro.api.session.Session.save` resumes mesh runs the way it
resumes sim runs (payload layout in DESIGN.md §12).

Optional ``worker_dilation`` multiplies worker k's *measured* time by a
constant factor — emulating a heterogeneous fleet (OmniLearn-style slow
executors) on homogeneous host hardware so the closed loop can be exercised
end-to-end.  The computation itself is always real.

Co-located serving (DESIGN.md §13): ``reserve`` withholds the top devices
of the data axis from training placement so a decode loop can own them
(`repro.train.colocate.ColocatedMeshTrainer`); :meth:`set_reserve` resizes
that region at runtime through the same replan path membership events use,
and :meth:`_charge_interference` lets the co-located trainer fold measured
decode seconds into a sharing worker's step time — decode interference
then looks to the controller exactly like resource heterogeneity.
"""

from __future__ import annotations

import dataclasses
import math
import time as _time
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import (
    SlicePlan,
    bucket_up,
    carve_serve,
    combine_weighted,
    combine_weighted_with_sqnorm,
    cost_aware_allocation,
    largest_remainder_round,
    make_controller,
    plan_slices,
    static_allocation,
)
from repro.core.grad import weighted_psum, weighted_psum_with_sqnorm
from repro.het.simulator import WorkerSpec
from repro.launch.mesh import data_axes
from repro.optim.optimizers import Optimizer
from repro.train import spans
from repro.train.engine import EventEngine
from repro.train.loop import OuterBatchMixin, StepRecord, TrainConfig


class _MeasuredTimeModel:
    """Measured-time stand-in for ``ClusterSim``: the event engine's clock.

    Duck-types the surface :class:`EventEngine` needs (``workers``,
    ``iteration_time``, ``bsp_step``, mutable ``time``) but is backed by
    EWMA per-example rates learned from real, device-synced completion
    measurements instead of a calibrated model — this is what lets the
    backend-agnostic engine drive ASP/elastic schedules on the mesh
    (DESIGN.md §12).
    """

    DEFAULT_RATE = 1e-3   # sec/example before any worker has been measured

    def __init__(self, num_workers: int, alpha: float) -> None:
        self.time = 0.0
        self.iteration = 0
        self.alpha = alpha
        self.rate: list[Optional[float]] = [None] * num_workers
        self._pending_round: Optional[list[float]] = None

    @property
    def workers(self) -> list:                 # engine reads len(sim.workers)
        return self.rate

    # -------------------------------------------------------- observations

    def observe(self, k: int, batch: int, seconds: float) -> None:
        """Fold one measured (dilated) completion into worker k's rate."""
        r = seconds / max(batch, 1)
        prev = self.rate[k]
        self.rate[k] = r if prev is None else (
            self.alpha * r + (1 - self.alpha) * prev)

    def iteration_time(self, k: int, batch: int,
                       at_time: Optional[float] = None) -> float:
        """Predicted step time from the EWMA rate (engine schedule source).

        Unmeasured workers (fresh joiners, cold start) borrow the mean
        measured rate so the event queue stays well-ordered until their
        first real completion lands.
        """
        r = self.rate[k]
        if r is None:
            known = [x for x in self.rate if x is not None]
            r = sum(known) / len(known) if known else self.DEFAULT_RATE
        return r * batch

    # ----------------------------------------------------------- BSP round

    def push_round(self, worker_times: Sequence[float]) -> None:
        """Stage one round's measured per-worker times for ``bsp_step``."""
        self._pending_round = list(worker_times)

    def bsp_step(self, batches: Sequence[int]) -> dict:
        """Engine-facing barrier: consumes the staged MEASURED times (the
        sim backend models these; here they were clocked on device)."""
        times = self._pending_round
        if times is None or len(times) != len(batches):
            raise RuntimeError(
                "bsp_step needs a staged measured round (push_round first)")
        self._pending_round = None
        t_iter = max(times)
        self.time += t_iter
        self.iteration += 1
        return {
            "worker_times": times,
            "iteration_time": t_iter,
            "straggler_waste": sum(t_iter - t for t in times) / max(
                len(times) * t_iter, 1e-9),
        }

    # ---------------------------------------------------------- membership

    def remove_worker(self, k: int) -> None:
        del self.rate[k]

    def add_worker(self) -> None:
        self.rate.append(None)


@dataclasses.dataclass
class _WorkerExec:
    """One worker's execution substrate: its (sub-)mesh + compiled calls."""

    mesh: Mesh
    daxes: tuple                   # batch-carrying axes of ``mesh``
    quantum: int                   # bucket quantum = slice data extent
    bucket_base: int               # ladder anchor (microbatch, quantized)
    gradfn: Callable               # jitted shard_map over ``mesh``
    slice: Optional[tuple[int, int]]   # (start, length) on the data axis;
                                       # None = full-axis fallback
    data_sharding: NamedSharding
    params_sharding: NamedSharding


@dataclasses.dataclass
class _Dispatch:
    """An in-flight (possibly still executing) worker gradient call."""

    worker: int
    out: tuple                     # (g_mean, loss_sum, w_sum, side) on device
    t0: float                      # dispatch timestamp (perf_counter)
    fresh_trace: bool              # this call paid for tracing+compilation
    host_data: object              # pre-transfer batch (for the solo rerun)
    mask_host: np.ndarray
    bucket: int


def _ready_timestamp(out) -> float:
    """Block until ``out`` is device-complete; return the completion time.

    Runs on an awaiter thread per in-flight worker so each slice's
    completion is stamped when *that slice* finishes, independent of the
    order the main thread would have polled them in.
    """
    jax.block_until_ready(out)
    return _time.perf_counter()


class MeshTrainer(OuterBatchMixin):
    """Drives the dynamic-batching loop on a real JAX mesh (BSP + ASP).

    Presents the same surface as :class:`HeterogeneousTrainer` to
    :class:`repro.api.session.Session` (``bsp_step`` / ``asp_step`` /
    ``history`` / ``batches`` / ``controller`` / ``engine`` / membership
    events / checkpoint state), but executes on ``mesh`` — concurrently
    over disjoint data-axis slices when the axis is wide enough
    (DESIGN.md §12) — and feeds the controller measured times.  Construct
    via :class:`repro.api.backend.MeshBackend`, not directly.
    """

    backend_kind = "mesh"

    def __init__(
        self,
        *,
        mesh,
        num_workers: int,
        init_params: Callable,
        loss_and_grad: Callable,
        next_batch: Callable,
        optimizer: Optimizer,
        cfg: TrainConfig,
        growth: float = 1.25,
        time_alpha: float = 0.5,
        worker_dilation: Optional[Sequence[float]] = None,
        dilation_for_spec: Optional[Callable[[WorkerSpec], float]] = None,
        concurrent: bool = True,
        reserve: int = 0,
    ):
        if num_workers < 1:
            raise ValueError("need at least one worker")
        self.cfg = cfg
        self.mesh = mesh
        self._daxes = data_axes(mesh)
        if not self._daxes:
            raise ValueError(f"mesh {mesh.axis_names} has no data axis")
        # train-region ladder anchors (the fallback path's quanta); slices
        # get their own per-worker quanta from the placement plan.  The top
        # ``reserve`` devices of the data axis belong to a co-located serve
        # slice (DESIGN.md §13) and never host training shards.
        self.data_extent = int(math.prod(mesh.shape[a] for a in self._daxes))
        if reserve < 0 or self.data_extent - reserve < 1:
            raise ValueError(
                f"reserving {reserve} of {self.data_extent} data-axis "
                f"devices for serving would leave no training devices — "
                f"training fully preempted; shrink the serve slice or "
                f"time-multiplex it (serve mode 'shared')")
        self.reserve = reserve
        self.train_extent = self.data_extent - reserve
        self.quantum = self.train_extent
        self.bucket_base = self.quantum * -(-cfg.microbatch // self.quantum)
        self.growth = growth
        self.time_alpha = time_alpha
        self.k = num_workers
        if worker_dilation is not None and len(worker_dilation) != num_workers:
            raise ValueError(
                f"{len(worker_dilation)} dilation factors for "
                f"{num_workers} workers")
        self.dilation = ([1.0] * num_workers if worker_dilation is None
                         else [float(d) for d in worker_dilation])
        self._dilation_for_spec = dilation_for_spec
        self.next_batch = next_batch
        self.optimizer = optimizer
        self._loss_and_grad = loss_and_grad
        key = jax.random.PRNGKey(cfg.seed)
        self.params = init_params(key)
        self.opt_state = optimizer.init(self.params)
        self.step_idx = 0
        self.history: list[StepRecord] = []
        self.membership_log: list[tuple[int, str, int]] = []
        # --- execution counters (mirror HeterogeneousTrainer's) ---
        self.accum_calls = 0       # jitted training executions
        self.accum_traces = 0      # XLA traces (one per distinct bucket)
        self.timing_reruns = 0     # post-compile re-executions (timing only)
        self.rows_valid = 0        # rows dispatched that the mask counts
        self.rows_padded = 0       # bucket rows past them (mask 0)
        # what the model counts in its steps (the held-expert layer's
        # ``expert_pairs``, summed, and ``expert_pairs_max``, the largest
        # one expert's load in one layer of one step), fetched with the
        # losses each round already reads
        self.model_counters: dict[str, int] = {}
        # gradient bytes put onto the full mesh after the workers' slices:
        # each leaf's bytes times the chips it was not on yet
        self.exchange_bytes = 0
        self._compiles0 = spans.compiles()
        # the BSP round in flight's seconds per span name (None outside)
        self._phases: Optional[dict] = None
        self._transfer = None      # the round's transfer awaiter (a future)
        # (dispatch_ts, completion_ts) per worker for the last concurrent
        # BSP round (concurrency diagnostics; None until one ran)
        self.last_round_stamps: Optional[list[tuple[float, float]]] = None
        # per worker, the distinct device sets its outputs of that round
        # live on (placement diagnostics: one set, its own slice's devices)
        self.last_round_devices: Optional[list[set[frozenset]]] = None
        self.worker_buckets: list[set[int]] = [set() for _ in range(self.k)]
        # --- slice placement + per-worker compiled calls ---
        # devices with the data axes flattened to the front: row i is the
        # i-th data-axis position (all model-axis columns at that position)
        dev = np.asarray(mesh.devices)
        names = list(mesh.axis_names)
        didx = [names.index(a) for a in self._daxes]
        oidx = [i for i in range(dev.ndim) if i not in didx]
        self._other_axes = tuple(names[i] for i in oidx)
        dev = np.transpose(dev, didx + oidx)
        self._flat_devices = dev.reshape(
            (self.data_extent,) + dev.shape[len(didx):])
        self._full_replicated = NamedSharding(mesh, P())
        # must precede _reconfigure_execution: _make_exec's worker_fn adds a
        # fourth |g_k|^2 output (DESIGN.md §15) when grad stats are needed
        self._need_grad_stats = cfg.global_batch.needs_grad_stats
        self._want_concurrent = bool(concurrent)
        self.concurrent = False
        self.slice_plan: Optional[SlicePlan] = None
        self._exec: list[_WorkerExec] = []
        self._pool: Optional[ThreadPoolExecutor] = None
        self._pool_size = 0
        self._transfer_thread: Optional[ThreadPoolExecutor] = None
        self._reconfigure_execution()
        # --- measurement state + event queue ---
        self._ewma: list[Optional[float]] = [None] * self.k
        self.time_model = _MeasuredTimeModel(self.k, time_alpha)
        self.sim = self.time_model   # Session/metrics read trainer.sim.time
        self._opt_update = jax.jit(optimizer.update)
        self._opt_jit_cache = {}  # LR-coupling: one jitted update per scale
        self.batches = self._initial_batches()
        self.engine = EventEngine(self.time_model)
        self.controller = None
        if cfg.batching == "dynamic":
            self.controller = make_controller(self.batches, cfg.controller)
        self._init_outer()
        self._outer_last_time = self.time_model.time

    # ----------------------------------------------------- execution setup

    def _make_exec(self, mesh_obj: Mesh, daxes: tuple,
                   slice_: Optional[tuple[int, int]]) -> _WorkerExec:
        """Jitted shard_map over ``mesh_obj``: masked local grad sums +
        ``weighted_psum`` (gradient-exactness argument: DESIGN.md §11-§12).

        Rows of the padded batch are sharded over ``daxes``; each shard
        differentiates the masked SUM loss of its rows, and the single
        cross-shard division by the global mask-weight sum realizes the
        Eq. 2-3 weighted mean exactly (padding rows: mask 0 => zero grad,
        zero weight).  One XLA trace per distinct bucket shape per slice.
        """
        quantum = int(math.prod(mesh_obj.shape[a] for a in daxes))
        bucket_base = quantum * -(-self.cfg.microbatch // quantum)
        loss_and_grad = self._loss_and_grad

        need_stats = self._need_grad_stats

        def worker_fn(params, batch, mask):
            self.accum_traces += 1  # python side effect: runs at trace time
            (loss_sum, w_sum, aux), grads = loss_and_grad(
                params, batch, mask)
            # side outputs: the model's counters (an aux dict's entries
            # beside its loss, lm_workload), summed over the shards or the
            # largest for ``*_max``, and |g_k|^2 for the GNS estimator
            side = {k: (jax.lax.pmax if k.endswith("_max") else
                        jax.lax.psum)(v, daxes)
                    for k, v in (aux.items() if isinstance(aux, dict)
                                 else ()) if k != "aux_loss"}
            if need_stats:
                # |g_k|^2 side stat for the GNS estimator rides the
                # existing psum call (DESIGN.md §15) — no extra pass
                g_mean, side["sqnorm"] = weighted_psum_with_sqnorm(
                    grads, w_sum, daxes)
            else:
                g_mean = weighted_psum(grads, w_sum, daxes)
            return (g_mean, jax.lax.psum(loss_sum, daxes),
                    jax.lax.psum(w_sum, daxes), side)

        sharded = jax.shard_map(
            worker_fn, mesh=mesh_obj,
            in_specs=(P(), P(daxes), P(daxes)),
            out_specs=(P(), P(), P(), P()),
            # grads ARE replicated over non-data axes (identical inputs and
            # deterministic compute per slice), but the Pallas kernel's
            # outputs carry no varying-axis type, so the check is off
            check_vma=False)
        return _WorkerExec(
            mesh=mesh_obj, daxes=daxes, quantum=quantum,
            bucket_base=bucket_base,
            gradfn=jax.jit(sharded),
            slice=slice_,
            data_sharding=NamedSharding(mesh_obj, P(daxes)),
            params_sharding=NamedSharding(mesh_obj, P()),
        )

    def _reconfigure_execution(
            self, plan: Optional[SlicePlan] = None) -> None:
        """(Re)build per-worker execution records for the current k.

        Concurrent mode when the data axis has at least one device per
        worker; otherwise all workers time-multiplex one full-axis record.
        Unchanged slices keep their record (and its jit cache); workers
        whose placement changed get a fresh record and a cleared bucket
        set — their old compiled shapes no longer apply (DESIGN.md §12).
        """
        old = list(self._exec)
        was_concurrent = self.concurrent
        concurrent = self._want_concurrent and self.k <= self.train_extent
        if concurrent and plan is None:
            # equal device shares: the heterogeneity lives in the batch
            # sizes, not the slice widths, so slices stay maximally stable.
            # A live serve reserve routes through the placement layer's
            # carve (DESIGN.md §13) so the dedicated-slice split has one
            # source of truth.
            if self.reserve:
                plan, _ = carve_serve(self.data_extent, self.k,
                                      self.reserve)
            else:
                plan = plan_slices(self.train_extent, self.k)
        self.concurrent = concurrent
        self.slice_plan = plan if concurrent else None
        if not concurrent:
            # the fallback record is reusable only while the train region
            # is unchanged (a serve-slice resize changes its quantum)
            if old and not was_concurrent \
                    and old[0].quantum == self.train_extent:
                shared = old[0]
            elif self.reserve == 0:
                shared = self._make_exec(self.mesh, self._daxes, None)
            else:
                sub = self._flat_devices[:self.train_extent]
                submesh = Mesh(sub, ("data",) + self._other_axes)
                shared = self._make_exec(submesh, ("data",), None)
            new = [shared] * self.k
        else:
            by_slice = {rec.slice: rec for rec in old} if was_concurrent \
                else {}
            new = []
            for start, length in self.slice_plan.slices:
                rec = by_slice.get((start, length))
                if rec is None:
                    sub = self._flat_devices[start:start + length]
                    submesh = Mesh(sub, ("data",) + self._other_axes)
                    rec = self._make_exec(submesh, ("data",), (start, length))
                new.append(rec)
        for j in range(min(len(old), self.k)):
            if new[j] is not old[j]:
                self.worker_buckets[j] = set()
        self._exec = new

    def _await_pool(self) -> ThreadPoolExecutor:
        """Awaiter threads (one per in-flight worker) for completion
        timestamps; grown on membership so no await ever queues."""
        if self._pool is None or self._pool_size < self.k:
            if self._pool is not None:
                self._pool.shutdown(wait=False)
            self._pool_size = max(self.k, 4)
            self._pool = ThreadPoolExecutor(
                max_workers=self._pool_size, thread_name_prefix="mesh-await")
        return self._pool

    def _transfer_pool(self) -> ThreadPoolExecutor:
        """The transfer span's own thread: it may still wait on a round's
        moved gradients when the next round's awaiters start, so it must
        not hold one of theirs."""
        if self._transfer_thread is None:
            self._transfer_thread = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="mesh-transfer")
        return self._transfer_thread

    def _span(self, name: str, **ids):
        """A span of the round (``repro.train.spans``), its seconds added
        to the round's phases while a BSP round is in flight."""
        return spans.span(name, self._phases, round=self.step_idx, **ids)

    @property
    def compiles(self) -> int:
        """Backend compiles in this process since the trainer was built."""
        return spans.compiles()[0] - self._compiles0[0]

    @property
    def compile_s(self) -> float:
        """Their seconds."""
        return spans.compiles()[1] - self._compiles0[1]

    # ------------------------------------------------------------- planning

    def bucket_for(self, worker: int, batch: int) -> int:
        """Worker's ladder rung for ``batch`` (anchored at its slice)."""
        rec = self._exec[worker]
        return bucket_up(batch, base=rec.bucket_base, growth=self.growth,
                         quantum=rec.quantum)

    def compiled_step(self, worker: int, batch):
        """``worker``'s compiled gradient step for ``batch`` (a pytree of
        arrays or ``jax.ShapeDtypeStruct``; leading dim a bucket of its
        ladder).  The same program its rounds run, so a warm compile cache
        serves it; for inspecting the compiled text or memory."""
        rec = self._exec[worker]

        def spec(x, sharding):
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)

        params = jax.tree.map(lambda x: spec(x, rec.params_sharding),
                              self.params)
        data = jax.tree.map(lambda x: spec(x, rec.data_sharding), batch)
        rows = jax.tree.leaves(batch)[0].shape[0]
        mask = jax.ShapeDtypeStruct((rows,), jnp.float32,
                                    sharding=rec.data_sharding)
        return rec.gradfn.lower(params, data, mask).compile()

    def bucket(self, batch: int) -> int:
        """Full-axis ladder rung (the fallback path's shape for ``batch``)."""
        return bucket_up(batch, base=self.bucket_base, growth=self.growth,
                         quantum=self.quantum)

    def _initial_batches(self) -> list[int]:
        cfg = self.cfg
        outer_active = (cfg.batching == "dynamic"
                        and cfg.global_batch.kind != "fixed")
        if cfg.batching == "uniform" or (
            cfg.batching == "dynamic" and cfg.init_allocation == "uniform"
            and not outer_active
        ):
            return [cfg.b0] * self.k
        # open-loop init on real hardware: a PROBE round (one measured step
        # per worker at b0, gradients discarded) replaces the simulator's
        # peek_throughput model — the mesh analogue of §III-B's estimate.
        # The measurements also seed the event engine's rate model, so an
        # ASP run's first schedule is already measurement-ordered.
        times = []
        for k in range(self.k):
            t = self._measured_worker_grad(k, cfg.b0)[3]
            self.time_model.observe(k, cfg.b0, t)
            times.append(t)
        if outer_active:
            # the outer controller's initial B_global goes through the
            # price/capacity-aware allocator (DESIGN.md §15); real hardware
            # exposes no memory-cliff capacities or spot prices, so this
            # reduces to the measured-throughput split of K*b0
            return cost_aware_allocation(
                [cfg.b0 / t for t in times], self.k * cfg.b0)
        return static_allocation([cfg.b0 / t for t in times], cfg.b0)

    # ------------------------------------------------------------ gradients

    def _dispatch(self, worker: int, batch_size: int) -> _Dispatch:
        """Launch one worker's bucketed gradient call WITHOUT blocking.

        Fetches bucket-many examples and masks the tail (the same
        fetch-padded-then-mask idiom as the sim path's remainder
        microbatch, so the first b_k stream examples are identical to an
        unpadded fetch), places data on the worker's slice, and returns
        with the call still in flight — JAX async dispatch unblocked.

        SUFFIX-PADDING CONTRACT (DESIGN.md §14): the mask built here —
        ``arange(bucket) < batch_size`` — is the single source of truth for
        which rows are real.  Valid rows always form a *prefix*; padding is
        always a suffix.  Kernel-enabled workloads (api/workload.py
        ``lm_workload(use_kernel=True)``) recover the ragged kernel's
        ``num_valid`` by counting this mask's nonzero rows, so the rows the
        loss masks out are exactly the rows the Pallas grid skips.  The
        contract survives data-axis sharding: each shard holds a contiguous
        chunk of rows, and a global prefix restricted to a contiguous chunk
        is still a prefix.  Don't reorder rows here without updating that
        derivation.
        """
        with self._span("dispatch", worker=worker):
            rec = self._exec[worker]
            bucket = self.bucket_for(worker, batch_size)
            self.worker_buckets[worker].add(bucket)
            self.rows_valid += batch_size
            self.rows_padded += bucket - batch_size
            host_data = self.next_batch(worker, bucket)
            mask_host = (np.arange(bucket) < batch_size).astype(np.float32)
            data = jax.tree_util.tree_map(
                lambda x: jax.device_put(x, rec.data_sharding), host_data)
            mask = jax.device_put(jnp.asarray(mask_host), rec.data_sharding)
            # pin params to ONE canonical sharding (replicated over the
            # worker's mesh): each slice needs its own replica anyway (a
            # per-slice jit may not mix device sets with the full mesh), and
            # a drifting input sharding (uncommitted init params vs committed
            # post-update params) would trigger silent re-LOWERS —
            # recompiles with no fresh trace — that the compile-time
            # exclusion below could not detect
            params = jax.device_put(self.params, rec.params_sharding)
            traces_before = self.accum_traces
            t0 = _time.perf_counter()
            out = rec.gradfn(params, data, mask)
            self.accum_calls += 1
        return _Dispatch(
            worker=worker, out=out, t0=t0,
            fresh_trace=self.accum_traces > traces_before,
            host_data=host_data, mask_host=mask_host, bucket=bucket)

    def _solo_rerun(self, d: _Dispatch) -> float:
        """Compile-free timing: the first execution at a bucket paid for
        tracing+compilation, so re-run once, alone, from the same host data
        (pure function — result identical and discarded)."""
        self.timing_reruns += 1
        with self._span("rerun", worker=d.worker):
            rec = self._exec[d.worker]
            data = jax.tree_util.tree_map(
                lambda x: jax.device_put(x, rec.data_sharding), d.host_data)
            mask = jax.device_put(jnp.asarray(d.mask_host),
                                  rec.data_sharding)
            params = jax.device_put(self.params, rec.params_sharding)
            t0 = _time.perf_counter()
            rerun = rec.gradfn(params, data, mask)
            jax.block_until_ready(rerun)
            return _time.perf_counter() - t0

    def _measured_worker_grad(self, worker: int, batch_size: int):
        """One device-synced, timed gradient call for ``worker`` (solo).

        Returns ``(g_mean, loss_sum, weight_sum, seconds)`` where seconds is
        the compile-free, dilation-adjusted wall time of the execution.
        The ASP path, the probe round, and the sequential fallback all come
        through here; concurrent BSP rounds use ``_dispatch`` directly.
        """
        d = self._dispatch(worker, batch_size)
        with self._span("await", worker=worker):
            jax.block_until_ready(d.out)
            dt = _time.perf_counter() - d.t0
            loss_sum, w_sum, side = self._fetch_side(d)
            self._last_sqnorm = side.get("sqnorm")
        if d.fresh_trace:
            dt = self._solo_rerun(d)
        return d.out[0], loss_sum, w_sum, dt * self.dilation[worker]

    def _fetch_side(self, d: _Dispatch) -> tuple[float, float, dict]:
        """A finished call's loss and weight sums and side outputs, in one
        fetch; its counters are added to :attr:`model_counters`."""
        loss_sum, w_sum, side = jax.device_get(d.out[1:])
        side = {k: float(v) if k == "sqnorm" else int(v)
                for k, v in side.items()}
        for k, v in side.items():
            if k != "sqnorm":
                have = self.model_counters.get(k, 0)
                self.model_counters[k] = (max(have, v) if k.endswith("_max")
                                          else have + v)
        return float(loss_sum), float(w_sum), side

    def _observe_time(self, worker: int, seconds: float) -> float:
        """EWMA filter over measured step times (measurement pipeline; the
        controller applies its own ``ewma_alpha`` smoothing on top)."""
        prev = self._ewma[worker]
        cur = seconds if prev is None else (
            self.time_alpha * seconds + (1 - self.time_alpha) * prev)
        self._ewma[worker] = cur
        return cur

    # ------------------------------------------------------------------ BSP

    def _round_concurrent(self):
        """All workers in flight at once; max-of-workers wall time.

        Dispatch is async (no device syncs between launches), then one
        awaiter thread per worker stamps that slice's completion the moment
        it lands.  Per-worker time = own completion − own dispatch; workers
        that compiled this round get a solo rerun for clean timing.

        Split into :meth:`_dispatch_round` / :meth:`_collect_round` so the
        co-located trainer (DESIGN.md §13) can run decode work on its
        dedicated serve slice *while* the training calls are in flight.
        """
        return self._collect_round(self._dispatch_round())

    def _dispatch_round(self) -> list[_Dispatch]:
        """Launch every worker's bucketed call without blocking."""
        return [self._dispatch(k, self.batches[k]) for k in range(self.k)]

    def _submit_awaiters(self, dispatches: list[_Dispatch]) -> list:
        """Start one awaiter per in-flight worker NOW, so completions are
        stamped the moment they land even if the main thread goes on to do
        other work (the co-located trainer runs its decode loop here)."""
        pool = self._await_pool()
        return [pool.submit(_ready_timestamp, d.out) for d in dispatches]

    def _collect_round(self, dispatches: list[_Dispatch], futures=None):
        """Stamp per-slice completions; gather grads, losses, raw times."""
        if futures is None:
            futures = self._submit_awaiters(dispatches)
        with self._span("await"):
            stamps = [f.result() for f in futures]
        # (dispatch, completion) per worker, for concurrency diagnostics:
        # max(dispatch) < min(completion) ⇔ all K calls were in flight at
        # once (benchmarks/backend_bench.py asserts this)
        self.last_round_stamps = [(d.t0, done)
                                  for d, done in zip(dispatches, stamps)]
        self.last_round_devices = [
            {frozenset(x.sharding.device_set) for x in jax.tree.leaves(d.out)}
            for d in dispatches]
        raw_times = [
            (self._solo_rerun(d) if d.fresh_trace else done - d.t0)
            * self.dilation[d.worker] for d, done in zip(dispatches, stamps)]
        grads, losses, weights, sqnorms = [], 0.0, 0.0, []
        with self._span("exchange"):
            t_issue = _time.perf_counter()
            to = self._full_replicated.device_set
            for d in dispatches:
                g_mean = d.out[0]
                loss_sum, w_sum, side = self._fetch_side(d)
                # slice-committed grads must rejoin the full mesh before the
                # host-side lambda combine
                grads.append(jax.device_put(g_mean, self._full_replicated))
                self.exchange_bytes += sum(
                    x.nbytes * len(to - x.sharding.device_set)
                    for x in jax.tree.leaves(g_mean))
                losses += loss_sum
                weights += w_sum
                if "sqnorm" in side:
                    sqnorms.append(side["sqnorm"])
            # the moved gradients are no XLA op: a span on a thread of its
            # own covers them until they are ready on every chip
            self._transfer = self._transfer_pool().submit(
                spans.span_until_ready, "transfer", grads, t_issue,
                round=self.step_idx)
        return grads, losses, weights, raw_times, sqnorms

    def _round_sequential(self):
        """Fallback: time-multiplex the full data axis (sum-of-workers)."""
        grads, losses, weights, raw_times, sqnorms = [], 0.0, 0.0, [], []
        for k in range(self.k):
            g, ls, ws, dt = self._measured_worker_grad(k, self.batches[k])
            grads.append(g)
            losses += ls
            weights += ws
            raw_times.append(dt)
            if self._last_sqnorm is not None:
                sqnorms.append(self._last_sqnorm)
        return grads, losses, weights, raw_times, sqnorms

    def _charge_interference(self, raw_times: list[float]) -> list[float]:
        """Hook: the co-located trainer (DESIGN.md §13) adds measured decode
        seconds to the worker whose devices the serve slice time-multiplexes,
        so the controller, the engine clock, and the step records all see
        the interference consistently.  Base trainer: no-op."""
        return raw_times

    def bsp_step(self) -> StepRecord:
        self._phases = phases = {}
        pre_batches = list(self.batches)
        if self.concurrent and self.k > 1:
            grads, losses, weights, raw_times, sqnorms = \
                self._round_concurrent()
        else:
            grads, losses, weights, raw_times, sqnorms = \
                self._round_sequential()
        with self._span("control"):
            raw_times = self._charge_interference(raw_times)
            smoothed = [self._observe_time(k, t)
                        for k, t in enumerate(raw_times)]
            for k, t in enumerate(raw_times):
                self.time_model.observe(k, self.batches[k], t)
        with self._span("combine"):
            # Eq. 2-3: lambda-weighted combine (identical to the sim path)
            if self._need_grad_stats:
                g, g_sqnorm = combine_weighted_with_sqnorm(grads,
                                                           self.batches)
                g_sqnorm = float(g_sqnorm)
            else:
                g = combine_weighted(grads, self.batches)
                g_sqnorm = None
            if self.reserve and not self.concurrent:
                # fallback grads live on the train-region submesh (the
                # serve reserve is excluded); rejoin the full mesh so params
                # stay replicated everywhere across serve-slice resizes
                g = jax.device_put(g, self._full_replicated)
        with self._span("update"):
            self.params, self.opt_state = self._opt_update(
                self.params, g, self.opt_state, jnp.asarray(self.step_idx))
        with self._span("control"):
            # the engine's barrier consumes the round's MEASURED times (same
            # semantics as the sim backend's StepRecord) and keeps the
            # shared version counter BSP and ASP staleness both read; only
            # the controller sees the EWMA-filtered view
            self.time_model.push_round(raw_times)
            info = self.engine.bsp_round(self.batches)
            adjusted = False
            if self.controller is not None:
                upd = self.controller.observe(smoothed)
                adjusted = upd.updated
                self.batches = upd.batches
            if self._observe_outer(
                    loss=losses / max(weights, 1e-9),
                    seconds=info["iteration_time"],
                    sqnorms=sqnorms or None, pre_batches=pre_batches,
                    combined_sqnorm=g_sqnorm,
                    worker_times=raw_times):
                # a B_global resize needs NO slice replan: slices keep their
                # widths, each worker's grown batch just walks its own
                # bucket ladder — the §11 recompile bound is the ladder
                # length
                adjusted = True
        self._phases = None
        rec = StepRecord(
            step=self.step_idx,
            sim_time=self.time_model.time,
            iteration_time=info["iteration_time"],
            loss=losses / max(weights, 1e-9),
            batches=list(self.batches),
            adjusted=adjusted,
            straggler_waste=info["straggler_waste"],
            worker_times=list(raw_times),
            phases=phases,
        )
        if self._transfer is not None:
            # a fresh dict, not an update of the one readers may hold
            self._transfer.add_done_callback(
                lambda f: setattr(rec, "phases",
                                  {**rec.phases, "transfer": f.result()}))
            self._transfer = None
        self.history.append(rec)
        self.step_idx += 1
        return rec

    # ------------------------------------------------------------------ ASP

    def asp_step(self) -> StepRecord:
        """One global ASP update on the mesh (DESIGN.md §12 event flow).

        The event engine pops the predicted-earliest completion (per-worker
        EWMA rates learned from real measurements); that worker's gradient
        is computed — for real, on its slice — against the params it last
        read, applied with the paper's staleness-weighted lambda scaling,
        and the measured duration updates the rate model so the emulated
        timeline tracks the hardware.  Identical staleness/versioning
        semantics to ``HeterogeneousTrainer.asp_step`` (the queue is the
        same ``EventEngine``).
        """
        eng = self.engine
        if not eng.scheduled:
            eng.asp_schedule(self.batches, payload=self.params)
        ev = eng.asp_next(self.batches)
        i = ev.worker
        # gradient on stale params (the params this worker last read)
        saved = self.params
        self.params = eng.get_payload(i)
        g, ls, ws, dt = self._measured_worker_grad(i, self.batches[i])
        self.params = saved
        self._observe_time(i, dt)
        self.time_model.observe(i, self.batches[i], dt)
        lam = self.batches[i] / sum(self.batches)
        g = jax.tree_util.tree_map(lambda x: lam * self.k * x, g)
        if self.concurrent or self.reserve:
            g = jax.device_put(g, self._full_replicated)
        self.params, self.opt_state = self._opt_update(
            self.params, g, self.opt_state, jnp.asarray(self.step_idx))
        eng.set_payload(i, self.params)
        adjusted = False
        if self.controller is not None and eng.version % self.k == 0:
            # observe each worker's expected iteration time from the rate
            # model — prediction, not a fresh measurement, mirroring the
            # sim path's RNG-free peek
            times = [self.time_model.iteration_time(j, self.batches[j])
                     for j in range(self.k)]
            upd = self.controller.observe(times)
            adjusted = upd.updated
            self.batches = upd.batches
        if self.outer is not None and eng.version % self.k == 0:
            # same cadence as the inner observe (~one whole-cluster sweep);
            # gns is BSP-only (config-validated), so no stats here
            elapsed = self.time_model.time - self._outer_last_time
            self._outer_last_time = self.time_model.time
            if self._observe_outer(loss=ls / max(ws, 1e-9),
                                   seconds=max(elapsed, 0.0)):
                adjusted = True
        rec = StepRecord(
            step=self.step_idx, sim_time=self.time_model.time,
            iteration_time=float(ev.time), loss=ls / max(ws, 1e-9),
            batches=list(self.batches), adjusted=adjusted,
            straggler_waste=float(ev.staleness),
        )
        self.history.append(rec)
        self.step_idx += 1
        return rec

    # ------------------------------------------------------------ membership

    def _measured_replan(self, total: int) -> list[int]:
        """Throughput-proportional split of the invariant global batch from
        MEASURED times (no controller attached).  Workers without a
        measurement yet (fresh joiners) get the mean throughput."""
        xput = [self.batches[i] / self._ewma[i]
                if i < len(self.batches) and self._ewma[i] else None
                for i in range(self.k)]
        known = [x for x in xput if x is not None] or [1.0]
        mean = sum(known) / len(known)
        xput = [mean if x is None else x for x in xput]
        s = sum(xput)
        return largest_remainder_round([total * x / s for x in xput],
                                       total, lo=1)

    def remove_worker(self, k: int) -> None:
        """Preemption of worker k; its batch share is reabsorbed (Σb_k
        invariant), survivors keep controller + measurement state, and the
        departed worker's devices rejoin the survivors' slices."""
        if self.k <= 1:
            raise ValueError("cannot remove the last worker")
        if not (0 <= k < self.k):
            raise ValueError(f"no worker {k} in a {self.k}-cluster")
        self.membership_log.append((self.step_idx, "remove", k))
        total = sum(self.batches)
        del self._ewma[k], self.dilation[k], self.worker_buckets[k]
        del self._exec[k]
        self.time_model.remove_worker(k)
        self.engine.remove_worker(k)
        # keep survivor indices aligned with the measurement state before
        # any replan reads batches[i]/ewma[i] pairs
        self.batches = [b for j, b in enumerate(self.batches) if j != k]
        self.k -= 1
        if self.controller is not None:
            self.batches = self.controller.remove_worker(k)
        else:
            self.batches = self._measured_replan(total)
        self._reconfigure_execution(
            self.slice_plan.remove(k) if self.slice_plan is not None
            else None)

    def add_worker(self, spec: WorkerSpec) -> None:
        """A replacement joins on the same mesh and gets a carved-out slice
        (model state is already replicated).  ``spec`` resources don't
        change real hardware; they seed the newcomer's dilation when
        heterogeneity is being emulated (see
        :class:`repro.api.backend.MeshBackend`)."""
        self.membership_log.append((self.step_idx, "add", self.k))
        total = (self.controller.global_batch if self.controller is not None
                 else sum(self.batches))
        self.k += 1
        self._ewma.append(None)
        self.worker_buckets.append(set())
        self.dilation.append(self._dilation_for_spec(spec)
                             if self._dilation_for_spec is not None else 1.0)
        self.time_model.add_worker()
        if self.controller is not None:
            self.batches = self.controller.add_worker(total / self.k)
        else:
            self.batches = self._measured_replan(total)
        self._reconfigure_execution(
            self.slice_plan.add() if (self.slice_plan is not None
                                      and self.k <= self.train_extent)
            else None)
        # the newcomer reads the CURRENT params and, if an ASP schedule is
        # live, dispatches immediately (predicted via the rate-model mean)
        self.engine.add_worker(self.batches[-1], payload=self.params)

    def slow_worker(self, k: int, factor: float) -> None:
        """Mesh half of :class:`repro.api.cluster.SlowWorker` (DESIGN.md
        §16): scales worker ``k``'s emulation dilation, the same knob
        ``MeshBackend(dilation=...)`` uses for declared heterogeneity — the
        measured control signal slows down exactly like a degrading spot
        instance would.  Factors compose; the reciprocal restores.  The
        dilation vector is part of ``exec_state_dict``, so a mid-degrade
        checkpoint resumes with the slowdown intact."""
        if not (0 <= k < self.k):
            raise ValueError(f"no worker {k} in a {self.k}-cluster")
        if not (factor > 0):
            raise ValueError(f"slowdown factor must be positive, got {factor}")
        self.dilation[k] = self.dilation[k] * float(factor)

    def reallocate_cost_aware(self) -> list[int]:
        """Churn replan (DESIGN.md §16) from MEASURED throughput.

        The mesh analogue of ``ElasticTrainer.reallocate_cost_aware``: real
        hardware exposes no simulator capacities or spot prices, so the
        cost-aware allocator reduces to the measured-throughput split —
        workers without a measurement yet (fresh joiners mid-storm) weigh
        in at the fleet mean.  Controller state is preserved via
        ``apply_allocation``; slices are NOT replanned (batch shares move,
        devices stay — resizes walk the existing bucket ladders, §11).
        """
        total = (self.controller.global_batch if self.controller is not None
                 else sum(self.batches))
        xput = [self.batches[i] / self._ewma[i]
                if i < len(self.batches) and self._ewma[i] else None
                for i in range(self.k)]
        known = [x for x in xput if x is not None] or [1.0]
        mean = sum(known) / len(known)
        xput = [mean if x is None else x for x in xput]
        b_min = (self.controller.config.b_min
                 if self.controller is not None else 1)
        plan = cost_aware_allocation(xput, total, b_min=b_min)
        self.membership_log.append((self.step_idx, "reallocate", -1))
        if self.controller is not None:
            self.batches = self.controller.apply_allocation(plan)
        else:
            self.batches = plan
        return self.batches

    def slice_devices(self, start: int, length: int) -> list:
        """First device of each data-axis row in ``[start, start+length)``.

        The serve region's per-row placement handles (DESIGN.md §17): the
        disaggregated decode path pins one :class:`repro.serve.slots.LMShard`
        per row, so the sharded KV slots genuinely live on distinct devices
        of the carved region rather than all on its first device.
        """
        if start < 0 or length < 1 or start + length > self.data_extent:
            raise ValueError(
                f"rows [{start}, {start + length}) outside the "
                f"{self.data_extent}-row data axis")
        return [np.ravel(self._flat_devices[i])[0]
                for i in range(start, start + length)]

    def set_reserve(self, n: int) -> None:
        """Resize the reserved serve region at the top of the data axis.

        The preemption policy's replan path (DESIGN.md §13): growing the
        reserve makes training *yield* devices to the serve slice, shrinking
        it returns freed capacity — in both directions worker slices replan
        through :meth:`_reconfigure_execution` exactly like a membership
        event, so controller and measurement state survive untouched and the
        batch controller re-equalizes around the new device shares.
        """
        if n == self.reserve:
            return
        if n < 0 or self.data_extent - n < 1:
            raise ValueError(
                f"reserving {n} of {self.data_extent} data-axis devices "
                f"would leave no training devices — training fully "
                f"preempted; the serve slice may not take the whole axis")
        self.reserve = n
        self.train_extent = self.data_extent - n
        self.quantum = self.train_extent
        self.bucket_base = self.quantum * -(-self.cfg.microbatch
                                            // self.quantum)
        self._reconfigure_execution()

    # ------------------------------------------------------------ checkpoint

    def exec_state_dict(self) -> dict:
        """Mesh execution state for ``Session.save`` (DESIGN.md §12):
        measurement EWMAs, the engine's rate model + clock, bucket-ladder
        caches, the slice assignment, and the dilation factors.  Everything
        here is JSON-serializable (the checkpoint metadata sidecar)."""
        return {
            "extent": self.data_extent,
            "reserve": self.reserve,
            "concurrent": self.concurrent,
            "slices": ([list(s) for s in self.slice_plan.slices]
                       if self.slice_plan is not None else None),
            "ewma": list(self._ewma),
            "rates": list(self.time_model.rate),
            "clock": {"time": self.time_model.time,
                      "iteration": self.time_model.iteration},
            "buckets": [sorted(b) for b in self.worker_buckets],
            "dilation": list(self.dilation),
        }

    def load_exec_state_dict(self, st: dict) -> None:
        """Inverse of :meth:`exec_state_dict` (bit-identical controller-
        facing state; compiled executables are re-traced lazily on the
        first post-restore dispatch per bucket)."""
        if int(st["extent"]) != self.data_extent:
            raise ValueError(
                f"checkpoint was taken on a mesh with data extent "
                f"{st['extent']}, this mesh has {self.data_extent} — "
                f"rebuild the Experiment on a matching mesh")
        # the serve reserve may have been resized by the preemption policy
        # since construction; restore it (and the train-region execution
        # records) before reconstructing the slice plan against train_extent
        self.set_reserve(int(st.get("reserve", 0)))
        slices = st["slices"]
        if bool(st["concurrent"]) != (slices is not None) or \
                (slices is None) != (self.slice_plan is None):
            raise ValueError(
                "checkpoint and session disagree on concurrent slicing "
                "(worker count vs data-axis width changed, or inconsistent "
                "checkpoint payload?)")
        if slices is not None:
            plan = SlicePlan(
                extent=self.train_extent, quantum=1,
                slices=tuple((int(a), int(b)) for a, b in slices))
            if plan.slices != self.slice_plan.slices:
                self._reconfigure_execution(plan)
        self._ewma = [None if v is None else float(v) for v in st["ewma"]]
        self.time_model.rate = [None if v is None else float(v)
                                for v in st["rates"]]
        self.time_model.time = float(st["clock"]["time"])
        self.time_model.iteration = int(st["clock"]["iteration"])
        self.worker_buckets = [set(int(x) for x in b)
                               for b in st["buckets"]]
        self.dilation = [float(d) for d in st["dilation"]]


def dilation_from_specs(specs: Sequence[WorkerSpec],
                        amdahl_p: float = 0.95):
    """Time-dilation factors emulating a ``ClusterSpec``'s declared
    heterogeneity on homogeneous hardware: the fastest declared worker runs
    undilated, a worker with half its effective speed takes 2x the measured
    time.  Effective speed = Amdahl(cores) x flops_ratio, the same model the
    simulator uses (DESIGN.md §2).

    Returns ``(dilations, dilation_for_spec)`` — the per-worker factors plus
    a function dilating any LATER-joining :class:`WorkerSpec` against the
    same reference (the initial fleet's fastest worker), so elastic joins
    stay on a consistent scale.
    """
    from repro.het.simulator import amdahl_speedup

    def eff(s: WorkerSpec) -> float:
        return amdahl_speedup(s.cores, amdahl_p) * s.flops_ratio

    top = max(eff(s) for s in specs)
    return [top / eff(s) for s in specs], lambda s: top / eff(s)
