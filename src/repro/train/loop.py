"""Heterogeneous data-parallel training loop (multislice mode).

K workers (heterogeneous simulated slices) each process a variable mini-batch
b_k as fixed-shape microbatches (core.batching); gradients are combined with
lambda_k weights (core.grad); iteration times come from the cluster simulator
(real SGD, simulated clock — DESIGN.md §2); a pluggable dynamic-batching
controller (core.control) replans {b_k} online.

Layering (DESIGN.md §1):
  * control   — core.control: P/PI/PID/gain-scheduled batch controllers;
  * execution — this module's jitted scan-based gradient accumulation:
    one compiled call per worker step over stacked fixed-shape microbatches,
    one device→host transfer per worker step (DESIGN.md §4);
  * sync      — train.engine: the event queue driving BSP and ASP;
  * elasticity— train.elastic: membership events that preserve state.

Batching policies (paper §III):
  * 'uniform'  — b_k = b0 for all workers (the baseline the paper beats);
  * 'static'   — open-loop throughput-proportional allocation (§III-B);
  * 'dynamic'  — static or uniform init + closed-loop controller (§III-C),
                 law selected by ``TrainConfig.controller.kind``.

Synchronisation: 'bsp' (barrier per iteration) or 'asp' (event-driven,
per-worker stale updates); both run through train.engine.
"""

from __future__ import annotations

import dataclasses
import time as _time
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    ControllerConfig,
    GlobalBatchConfig,
    GradStats,
    accumulate_microbatch_grads,
    combine_weighted,
    combine_weighted_with_sqnorm,
    cost_aware_allocation,
    global_batch_from_state_dict,
    largest_remainder_round,
    make_controller,
    make_global_controller,
    plan_microbatches,
    static_allocation,
    tree_sqnorm,
)
from repro.het.simulator import ClusterSim
from repro.optim.optimizers import Optimizer
from repro.optim.schedules import BatchCoupledSchedule
from repro.train.engine import EventEngine


@dataclasses.dataclass
class TrainConfig:
    b0: int = 32                     # per-worker nominal batch (global = K*b0)
    microbatch: int = 8              # fixed compiled shape
    batching: str = "dynamic"        # 'uniform' | 'static' | 'dynamic'
    init_allocation: str = "static"  # 'uniform' | 'static' (dynamic's start)
    sync: str = "bsp"                # 'bsp' | 'asp'
    controller: ControllerConfig = dataclasses.field(
        default_factory=ControllerConfig)
    global_batch: GlobalBatchConfig = dataclasses.field(
        default_factory=GlobalBatchConfig)
    max_steps: int = 1000
    target_loss: Optional[float] = None
    loss_ewma: float = 0.1           # smoothing for the stop criterion
    seed: int = 0
    log_every: int = 50

    _BATCHING = ("uniform", "static", "dynamic")
    _SYNC = ("bsp", "asp")
    _INIT_ALLOCATION = ("uniform", "static")

    def __post_init__(self) -> None:
        """Fail fast on typos: ``sync='asynch'`` used to silently run ASP's
        else-branch; now every enum-like field is validated."""
        if self.batching not in self._BATCHING:
            raise ValueError(
                f"batching must be one of {self._BATCHING}, got {self.batching!r}")
        if self.sync not in self._SYNC:
            raise ValueError(
                f"sync must be one of {self._SYNC}, got {self.sync!r}")
        if self.init_allocation not in self._INIT_ALLOCATION:
            raise ValueError(f"init_allocation must be one of "
                             f"{self._INIT_ALLOCATION}, got {self.init_allocation!r}")
        if self.b0 < 1:
            raise ValueError(f"b0 must be >= 1, got {self.b0}")
        if self.microbatch < 1:
            raise ValueError(f"microbatch must be >= 1, got {self.microbatch}")
        if self.microbatch > self.b0:
            raise ValueError(
                f"microbatch ({self.microbatch}) must be <= b0 ({self.b0})")
        if self.max_steps < 1:
            raise ValueError(f"max_steps must be >= 1, got {self.max_steps}")
        if not (0.0 < self.loss_ewma <= 1.0):
            raise ValueError(
                f"loss_ewma must be in (0, 1], got {self.loss_ewma}")
        if not isinstance(self.global_batch, GlobalBatchConfig):
            raise TypeError(
                f"global_batch must be a GlobalBatchConfig, "
                f"got {type(self.global_batch).__name__}")
        if self.global_batch.kind in ("gns", "dynamix") and self.sync != "bsp":
            raise ValueError(
                f"global_batch kind={self.global_batch.kind!r} consumes "
                "per-worker gradient moments of one BSP round; use "
                "sync='bsp' ('geometric'/'bandit' also run on ASP)")


@dataclasses.dataclass
class StepRecord:
    step: int
    sim_time: float
    iteration_time: float
    loss: float
    batches: list
    adjusted: bool
    straggler_waste: float
    worker_times: Optional[list] = None   # per-worker times (BSP rounds)
    # mesh BSP rounds: host seconds per span name (repro.train.spans); the
    # main thread's spans tile the round, ``transfer`` runs beside them on
    # a thread of its own and joins the dict when the moved gradients land
    phases: Optional[dict] = None


class OuterBatchMixin:
    """Two-level batch control glue shared by the sim and mesh trainers.

    Owns the outer B_global controller (DESIGN.md §15): construction (only
    for non-'fixed' kinds, so the fixed path stays bit-for-bit the
    pre-existing code), applying resizes through the inner controller's
    `set_global_batch`, coupling the LR schedule to the batch ratio, and
    checkpoint serde.  Host-side only; expects the host class to provide
    ``cfg``, ``batches``, ``controller``, ``optimizer``, ``k``, and
    ``_opt_update`` / ``_opt_jit_cache``.
    """

    outer = None

    def _init_outer(self) -> None:
        """Construct the outer controller (call once batches/controller exist).

        The ladder quantum is 1 so rung 0 equals the exact initial global
        batch — the first resize, not construction, is the first deviation
        from the fixed-batch trajectory.
        """
        cfg = self.cfg
        self.outer = None
        self._need_grad_stats = cfg.global_batch.needs_grad_stats
        if cfg.global_batch.kind == "fixed":
            return
        self.outer = make_global_controller(
            cfg.global_batch, b0=sum(self.batches), quantum=1)
        sched = getattr(self.optimizer, "schedule", None)
        if isinstance(sched, BatchCoupledSchedule):
            # reset a (possibly reused) coupled schedule to ratio 1 BEFORE
            # the first trace: jit bakes the host-float scale at trace time
            sched.set_batch_ratio(1.0)
            self._opt_jit_cache[1.0] = self._opt_update

    def _apply_global_batch(self, total: int) -> list[int]:
        """Commit an outer resize: rescale the split, re-couple the LR."""
        if self.controller is not None:
            self.batches = list(self.controller.set_global_batch(total))
        else:
            cur = sum(self.batches)
            self.batches = largest_remainder_round(
                [b * total / max(cur, 1) for b in self.batches],
                int(total), lo=1)
        self._couple_lr(total)
        return self.batches

    def _couple_lr(self, total: int) -> None:
        """Re-evaluate a batch-coupled LR schedule at the new B_global.

        jax.jit bakes the schedule's host-float scale into the compiled
        update at trace time, so each distinct scale gets its own jitted
        wrapper, cached — the cache (and hence the recompiles) is bounded by
        the number of ladder rungs.
        """
        if self.outer is None:
            return
        sched = getattr(self.optimizer, "schedule", None)
        if not isinstance(sched, BatchCoupledSchedule):
            return
        sched.set_batch_ratio(total / self.outer.b0)
        key = round(sched.scale, 12)
        if key not in self._opt_jit_cache:
            # a FRESH function object per scale: jax.jit keys its trace
            # cache on the wrapped callable, so jitting the same bound
            # `update` again would silently reuse the trace that baked the
            # old scale instead of re-reading sched.scale
            upd = self.optimizer.update

            def update(p, g, s, t):     # its program is jit_update
                return upd(p, g, s, t)

            self._opt_jit_cache[key] = jax.jit(update)
        self._opt_update = self._opt_jit_cache[key]

    def _worker_prices(self) -> Optional[list]:
        """Hook: per-worker spot prices for the outer context (or None)."""
        return None

    def _queue_signal(self) -> Optional[float]:
        """Hook: serve-queue depth for the outer context (or None)."""
        return None

    def _outer_context(self, worker_times=None) -> dict:
        """System context for context-aware outer kinds (DESIGN.md §18)."""
        ctx = {}
        if worker_times:
            ctx["worker_times"] = [float(t) for t in worker_times]
        prices = self._worker_prices()
        if prices:
            ctx["prices"] = [float(p) for p in prices]
        q = self._queue_signal()
        if q is not None:
            ctx["queue"] = float(q)
        return ctx

    def _observe_outer(self, *, loss: float, seconds: float,
                       sqnorms=None, pre_batches=None,
                       combined_sqnorm=None, worker_times=None) -> bool:
        """Feed the outer controller one step; apply a resize if it fires."""
        if self.outer is None:
            return False
        stats = None
        if self._need_grad_stats and sqnorms is not None:
            stats = GradStats(per_worker_sqnorm=list(sqnorms),
                              batches=list(pre_batches),
                              combined_sqnorm=float(combined_sqnorm))
        new_total = self.outer.observe(
            loss=loss, seconds=seconds, stats=stats,
            context=self._outer_context(worker_times))
        if new_total is None:
            return False
        self._apply_global_batch(new_total)
        return True

    def load_outer_state(self, state: dict) -> None:
        """Rebuild the outer controller from a checkpoint payload."""
        self.outer = global_batch_from_state_dict(state)
        self._need_grad_stats = self.outer.config.needs_grad_stats
        self._couple_lr(self.outer.b_global)


class HeterogeneousTrainer(OuterBatchMixin):
    """Drives (loss_and_grad, next_batch, optimizer) under simulated heterogeneity.

    loss_and_grad(params, batch, mask) -> ((loss_sum, w_sum, aux), grads)
        must be jit-compatible; called with fixed microbatch shapes only.
        CONTRACT: grads must be the gradient of the *weighted SUM* loss
        (loss_sum), NOT the mean — the trainer accumulates grad sums across
        microbatches and divides by the total weight once (exact Eq. 2-3
        weighting across variable microbatch counts).
    next_batch(worker, n) -> batch pytree with leading dim n.

    Execution is one jitted ``lax.scan`` over the worker's stacked
    microbatches per worker step: no per-microbatch Python dispatch, no
    per-microbatch host sync.  ``accum_calls`` counts jitted invocations
    and ``accum_traces`` counts (re)compilations — a new trace happens only
    when a worker's microbatch *count* changes, never when only its batch
    content changes.
    """

    backend_kind = "sim"   # checkpoint payload flavor (api.session.Session)

    def __init__(
        self,
        *,
        init_params: Callable,
        loss_and_grad: Callable,
        next_batch: Callable,
        optimizer: Optimizer,
        sim: ClusterSim,
        cfg: TrainConfig,
    ):
        self.cfg = cfg
        self.sim = sim
        self.k = len(sim.workers)
        self.next_batch = next_batch
        self.optimizer = optimizer
        key = jax.random.PRNGKey(cfg.seed)
        self.params = init_params(key)
        self.opt_state = optimizer.init(self.params)
        self.step_idx = 0
        self._need_grad_stats = cfg.global_batch.needs_grad_stats
        self._accum = self._build_accum(loss_and_grad)
        self._opt_update = jax.jit(optimizer.update)
        self._opt_jit_cache = {}  # LR-coupling: one jitted update per scale
        self.history: list[StepRecord] = []
        self.recompiles = 0
        self.accum_calls = 0      # jitted executions (one per worker step)
        self.accum_traces = 0     # XLA traces (one per distinct n_steps)
        self.engine = EventEngine(sim)
        self.batches = self._initial_batches()
        self.controller = None
        if cfg.batching == "dynamic":
            self.controller = make_controller(self.batches, cfg.controller)
        self._init_outer()
        self._outer_last_time = self.sim.time

    # ------------------------------------------------------------- planning

    def _worker_prices(self) -> Optional[list]:
        # spot prices live on the worker specs (het/spot.py keeps them
        # current through churn); the outer policy reads them as context
        return [w.price for w in self.sim.workers]

    def _initial_batches(self) -> list[int]:
        cfg = self.cfg
        if cfg.batching == "dynamic" and cfg.global_batch.kind != "fixed":
            # the outer controller's initial B_global goes through the
            # price/capacity-aware allocator (DESIGN.md §15) instead of the
            # uniform fallback: same RNG-free peek throughputs, plus each
            # worker's memory-cliff capacity and spot price from its spec
            xput = [self.sim.peek_throughput(i, cfg.b0) for i in range(self.k)]
            return cost_aware_allocation(
                xput, self.k * cfg.b0,
                capacities=[w.b_mem for w in self.sim.workers],
                prices=[w.price for w in self.sim.workers])
        if cfg.batching == "uniform" or (
            cfg.batching == "dynamic" and cfg.init_allocation == "uniform"
        ):
            return [cfg.b0] * self.k
        # open-loop: proportional to modelled worker throughput at b0.
        # This is an *estimate*, not simulated work: use the RNG-free peek
        # path so planning never perturbs the jitter stream.
        xput = [self.sim.peek_throughput(i, cfg.b0) for i in range(self.k)]
        return static_allocation(xput, cfg.b0)

    # --------------------------------------------------------- degradation

    def slow_worker(self, k: int, factor: float) -> None:
        """Multiplicative slowdown of worker ``k`` (``factor`` > 1 = slower).

        The sim-backend half of the :class:`repro.api.cluster.SlowWorker`
        event (DESIGN.md §16): scales the worker's modelled per-sample
        speed, so slow-degrading spot instances and transient stragglers
        hit the controller exactly like real interference would.  Factors
        compose; applying the reciprocal restores the worker bit-exactly.
        The spec is replaced, never mutated — a ``ClusterSpec`` that shares
        the spec object can still rebuild a pristine simulator.
        """
        if not (0 <= k < self.k):
            raise ValueError(f"no worker {k} in a {self.k}-cluster")
        if not (factor > 0):
            raise ValueError(f"slowdown factor must be positive, got {factor}")
        spec = self.sim.workers[k]
        self.sim.workers[k] = dataclasses.replace(
            spec, flops_ratio=spec.flops_ratio / factor)

    # ------------------------------------------------------------ gradients

    def _build_accum(self, loss_and_grad: Callable) -> Callable:
        """Jitted scan over stacked (n_steps, m, ...) microbatches.

        The scan carry accumulates gradient/loss/weight sums on device; the
        mean gradient (divide once by the total weight, Eq. 2-3) comes back
        with the loss sums in a single compiled call.  Buffers for the
        stacked data and masks are donated where the backend supports it.
        """

        def accum(params, data, masks):
            self.accum_traces += 1  # python side effect: runs at trace time
            g_sum, loss_sum, w_sum, _aux = accumulate_microbatch_grads(
                loss_and_grad, params, data, masks)
            # mean gradient over the worker's examples (divide ONCE)
            g_mean = jax.tree_util.tree_map(
                lambda g: g / jnp.maximum(w_sum, 1e-9), g_sum)
            if self._need_grad_stats:
                # |g_k|^2 side stat for the GNS estimator, inside the same
                # compiled call — estimation costs no extra pass
                return g_mean, loss_sum, w_sum, tree_sqnorm(g_mean)
            return g_mean, loss_sum, w_sum

        # donation is a no-op (with a warning) on CPU; only ask for it where
        # the backend can actually alias the stacked buffers
        donate = () if jax.default_backend() == "cpu" else (1, 2)
        return jax.jit(accum, donate_argnums=donate)

    def _worker_grad(self, worker: int, batch_size: int):
        """Real gradients for worker's b_k examples: ONE jitted call."""
        cfg = self.cfg
        plan = plan_microbatches(batch_size, cfg.microbatch)
        data = self.next_batch(worker, plan.padded_examples)
        stacked = jax.tree_util.tree_map(
            lambda x: jnp.reshape(x, (plan.n_steps, cfg.microbatch)
                                  + x.shape[1:]), data)
        masks = jnp.asarray(plan.masks())
        out = self._accum(self.params, stacked, masks)
        self.accum_calls += 1
        # single device->host transfer per worker step (g_mean stays on device)
        if self._need_grad_stats:
            g_mean, loss_sum, w_sum, sqn = out
            ls, ws, sq = jax.device_get((loss_sum, w_sum, sqn))
            self._last_sqnorm = float(sq)
        else:
            g_mean, loss_sum, w_sum = out
            ls, ws = jax.device_get((loss_sum, w_sum))
            self._last_sqnorm = None
        return g_mean, float(ls), float(ws)

    # ------------------------------------------------------------------ BSP

    def bsp_step(self) -> StepRecord:
        grads, losses, weights = [], 0.0, 0.0
        pre_batches = list(self.batches)
        sqnorms = []
        for k in range(self.k):
            g, ls, ws = self._worker_grad(k, self.batches[k])
            grads.append(g)
            losses += ls
            weights += ws
            if self._need_grad_stats:
                sqnorms.append(self._last_sqnorm)
        # Eq. 2-3: lambda-weighted combine
        if self._need_grad_stats:
            g, g_sqnorm = combine_weighted_with_sqnorm(grads, self.batches)
            g_sqnorm = float(g_sqnorm)
        else:
            g = combine_weighted(grads, self.batches)
            g_sqnorm = None
        self.params, self.opt_state = self._opt_update(
            self.params, g, self.opt_state, jnp.asarray(self.step_idx))
        info = self.engine.bsp_round(self.batches)
        adjusted = False
        if self.controller is not None:
            upd = self.controller.observe(info["worker_times"])
            adjusted = upd.updated
            self.batches = upd.batches
        if self._observe_outer(
                loss=losses / max(weights, 1e-9),
                seconds=info["iteration_time"],
                sqnorms=sqnorms or None, pre_batches=pre_batches,
                combined_sqnorm=g_sqnorm,
                worker_times=info["worker_times"]):
            adjusted = True
        rec = StepRecord(
            step=self.step_idx,
            sim_time=self.sim.time,
            iteration_time=info["iteration_time"],
            loss=losses / max(weights, 1e-9),
            batches=list(self.batches),
            adjusted=adjusted,
            straggler_waste=info["straggler_waste"],
            worker_times=list(info["worker_times"]),
        )
        self.history.append(rec)
        self.step_idx += 1
        return rec

    # ------------------------------------------------------------------ ASP

    def asp_step(self) -> StepRecord:
        """One global ASP update (next worker to finish pushes its gradient).

        True staleness: each worker's gradient is computed on the params it
        last read; the optimizer applies it whenever the worker finishes.
        The event queue (who finishes when, at which version) lives in the
        engine; this method only moves model state.
        """
        eng = self.engine
        if not eng.scheduled:
            eng.asp_schedule(self.batches, payload=self.params)
        ev = eng.asp_next(self.batches)
        i = ev.worker
        # gradient on stale params (the params this worker last read)
        saved = self.params
        self.params = eng.get_payload(i)
        g, ls, ws = self._worker_grad(i, self.batches[i])
        self.params = saved
        lam = self.batches[i] / sum(self.batches)
        g = jax.tree_util.tree_map(lambda x: lam * self.k * x, g)
        self.params, self.opt_state = self._opt_update(
            self.params, g, self.opt_state, jnp.asarray(self.step_idx))
        eng.set_payload(i, self.params)
        adjusted = False
        if self.controller is not None and eng.version % self.k == 0:
            # observe each worker's expected iteration time — RNG-free peek:
            # observation must not consume the jitter stream the engine's
            # event schedule draws from
            times = [self.sim.peek_iteration_time(j, self.batches[j])
                     for j in range(self.k)]
            upd = self.controller.observe(times)
            adjusted = upd.updated
            self.batches = upd.batches
        if self.outer is not None and eng.version % self.k == 0:
            # outer cadence matches the inner one: every K pushed versions
            # (~one whole-cluster sweep); gns is BSP-only (config-validated),
            # so no stats here — seconds are the simulated span of the sweep
            elapsed = self.sim.time - self._outer_last_time
            self._outer_last_time = self.sim.time
            if self._observe_outer(loss=ls / max(ws, 1e-9),
                                   seconds=max(elapsed, 0.0)):
                adjusted = True
        rec = StepRecord(
            step=self.step_idx, sim_time=self.sim.time,
            iteration_time=float(ev.time), loss=ls / max(ws, 1e-9),
            batches=list(self.batches), adjusted=adjusted,
            straggler_waste=float(ev.staleness),
        )
        self.history.append(rec)
        self.step_idx += 1
        return rec

    # ------------------------------------------------------------------ run

    def run(self) -> dict:
        cfg = self.cfg
        smoothed = None
        wall0 = _time.perf_counter()
        for _ in range(cfg.max_steps):
            rec = self.bsp_step() if cfg.sync == "bsp" else self.asp_step()
            smoothed = rec.loss if smoothed is None else (
                cfg.loss_ewma * rec.loss + (1 - cfg.loss_ewma) * smoothed)
            if cfg.target_loss is not None and smoothed <= cfg.target_loss:
                break
        return {
            "steps": self.step_idx,
            "sim_time": self.sim.time,
            "final_loss": smoothed,
            "reached_target": (cfg.target_loss is not None
                               and smoothed is not None
                               and smoothed <= cfg.target_loss),
            "wall_time": _time.perf_counter() - wall0,
            "batch_adjustments": (self.controller.num_updates
                                  if self.controller else 0),
            "outer_resizes": (self.outer.num_resizes
                              if self.outer is not None else 0),
            "history": self.history,
            "final_batches": list(self.batches),
        }
