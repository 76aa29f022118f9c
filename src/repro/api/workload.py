"""Workload: what to train — model init, loss, and a deterministic data feed.

The trainer's execution layer has a subtle contract (DESIGN.md §4): the
jit-compatible ``loss_and_grad(params, batch, mask)`` must return the
gradient of the *weighted SUM* loss, never the mean — gradient sums are
accumulated across microbatches and divided by the total weight exactly
once, which is what makes variable per-worker batch sizes weight examples
correctly (paper Eq. 2-3).  Before this module, that contract was a
six-line closure copy-pasted (comment included) across the launcher, every
example, and both benchmark modules.

This module implements it exactly once.  Users describe a workload in
ordinary terms:

  * :func:`mean_loss_workload` — write a plain per-example loss
    ``per_example_loss(params, batch) -> (n,)``; masking, summation, and
    the SUM-gradient contract are handled here.
  * :func:`sum_loss_workload` — for losses already in the repo's
    ``(loss_sum, weight_sum, aux)`` convention (``repro.models.simple``).
  * :func:`paper_workload` — the paper's LinReg / MNIST-CNN / ResNet
    workloads by name.
  * :func:`lm_workload` — transformer-LM training from a model config +
    ``DataPipeline`` (the launcher's path).

Every constructor bundles a deterministic per-(worker, step) batch source:
call *i* of worker *k* derives its key as ``fold_in(PRNGKey(seed + k), i)``,
so seeded runs are exactly reproducible and resumable (the source exposes
``state_dict``/``load_state_dict`` for Session checkpointing).
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp


@dataclasses.dataclass
class Workload:
    """Bundle satisfying the trainer contract: init + SUM-loss grad + data.

    ``loss_and_grad(params, batch, mask) -> ((loss_sum, w_sum, aux), grads)``
    with grads of the weighted SUM loss (use the adapters below rather than
    writing this by hand).  ``next_batch(worker, n)`` must return a pytree
    with leading dim ``n`` deterministically per (worker, call index).
    """

    name: str
    init: Callable
    loss_and_grad: Callable
    next_batch: Callable
    state_dict: Optional[Callable[[], dict]] = None
    load_state_dict: Optional[Callable[[dict], None]] = None


class CounterBatchSource:
    """Deterministic per-(worker, call) batch stream.

    Call *i* of worker *k* uses ``fold_in(PRNGKey(seed + k), i)`` — a pure
    function of (seed, worker, call index), so a controller batch-resize
    changes only ``n``, never which stream the examples come from, and a
    checkpoint can resume the stream exactly (``state_dict`` round-trips
    the per-worker counters).
    """

    def __init__(self, make_batch: Callable, seed: int = 0):
        self.make_batch = make_batch
        self.seed = seed
        self.counters: dict[int, int] = {}

    def __call__(self, worker: int, n: int):
        self.counters[worker] = self.counters.get(worker, 0) + 1
        key = jax.random.fold_in(jax.random.PRNGKey(self.seed + worker),
                                 self.counters[worker])
        return self.make_batch(key, n)

    def state_dict(self) -> dict:
        return {"seed": self.seed, "counters": dict(self.counters)}

    def load_state_dict(self, state: dict) -> None:
        if "seed" in state and int(state["seed"]) != self.seed:
            raise ValueError(
                f"checkpoint batch stream used seed {state['seed']}, this "
                f"workload uses {self.seed} — resuming would silently train "
                f"on a different data stream")
        self.counters = {int(k): int(v)
                         for k, v in state["counters"].items()}


# --------------------------------------------------------------- adapters


def sum_loss_adapter(loss_fn: Callable) -> Callable:
    """Trainer-contract ``loss_and_grad`` from a SUM-convention loss.

    ``loss_fn(params, batch, mask) -> (loss_sum, weight_sum, aux)``; the
    returned gradients are of ``loss_sum`` (THE single implementation of
    the SUM-semantics contract — see module docstring).
    """

    def loss_and_grad(params, batch, mask):
        def lf(p):
            ls, ws, aux = loss_fn(p, batch, mask)
            return ls, (ls, ws, aux)

        (_, metas), g = jax.value_and_grad(lf, has_aux=True)(params)
        return metas, g

    return loss_and_grad


def mean_loss_adapter(per_example_loss: Callable) -> Callable:
    """Trainer-contract ``loss_and_grad`` from an ordinary per-example loss.

    ``per_example_loss(params, batch) -> (n,)`` — one loss value per
    example, written as if computing a plain mean.  Masking (padded
    microbatch slots), summation, and the SUM-gradient contract happen
    here; the trainer divides by the total weight once per worker step.
    """

    def loss_and_grad(params, batch, mask):
        def lf(p):
            per_ex = per_example_loss(p, batch)
            ls = (per_ex * mask).sum()
            return ls, (ls, mask.sum(), jnp.zeros(()))

        (_, metas), g = jax.value_and_grad(lf, has_aux=True)(params)
        return metas, g

    return loss_and_grad


# ----------------------------------------------------------- constructors


def mean_loss_workload(name: str, init: Callable,
                       per_example_loss: Callable, make_batch: Callable,
                       *, seed: int = 0) -> Workload:
    """Workload from an ordinary per-example mean-style loss (see
    :func:`mean_loss_adapter`) + a ``make_batch(key, n)`` sampler."""
    src = CounterBatchSource(make_batch, seed)
    return Workload(name, init, mean_loss_adapter(per_example_loss), src,
                    src.state_dict, src.load_state_dict)


def sum_loss_workload(name: str, init: Callable, loss_fn: Callable,
                      make_batch: Callable, *, seed: int = 0) -> Workload:
    """Workload from a ``(loss_sum, weight_sum, aux)``-convention loss."""
    src = CounterBatchSource(make_batch, seed)
    return Workload(name, init, sum_loss_adapter(loss_fn), src,
                    src.state_dict, src.load_state_dict)


def paper_workload(name: str, *, seed: int = 100) -> Workload:
    """One of the paper's evaluation workloads ('linreg' | 'mnist-cnn' |
    'resnet'), on synthetic data with a planted ground truth."""
    from repro.models.simple import paper_workloads

    wl = paper_workloads()[name]
    return sum_loss_workload(name, wl.init, wl.loss_fn, wl.make_batch,
                             seed=seed)


def lm_workload(model_cfg, pipe, *, aux_weight: float = 0.0,
                use_kernel: bool = False) -> Workload:
    """Transformer-LM training from a model config + ``DataPipeline``.

    Handles both decoder-only and encoder-decoder families, optional
    modality prefixes, and an optional auxiliary-loss term weighted by
    ``aux_weight`` (e.g. MoE balance loss; scaled by the weight sum so it
    stays commensurate with the SUM-convention main loss).

    A model that counts (the held-expert layer's ``expert_pairs`` and
    ``expert_pairs_max``) reports aux as ``{"aux_loss": aux, **counters}``;
    the mesh trainer adds the counters up per round.

    ``use_kernel=True`` routes attention through the ragged Pallas kernel
    (``use_pallas``) and derives the kernel's ``num_valid`` from the very
    mask the trainer built when it padded the batch up the bucket ladder —
    one source of truth, so rows the loss masks out are exactly the rows
    the kernel grid skips (DESIGN.md §14).  Correct because the trainer's
    fetch contract pads as a *suffix* (valid rows form a prefix; this also
    holds shard-locally — a global prefix restricted to any contiguous
    data-shard chunk is still a prefix, see train/mesh.py).
    """
    from repro.models import encdec_loss, init_encdec, init_lm, lm_loss

    if use_kernel and model_cfg.family != "encdec":
        model_cfg = model_cfg.with_(use_pallas=True)
    init = init_encdec if model_cfg.family == "encdec" else init_lm

    def loss_and_grad(params, batch, mask):
        def lf(p):
            if model_cfg.family == "encdec":
                ls, ws, aux = encdec_loss(p, model_cfg, batch["prefix"],
                                          batch["tokens"], batch["targets"],
                                          mask)
                counters = {}
            else:
                num_valid = None
                if use_kernel:
                    row_w = mask if mask.ndim == 1 else mask.max(axis=-1)
                    num_valid = (row_w > 0).sum().astype(jnp.int32)
                ls, ws, aux, counters = lm_loss(
                    p, model_cfg, batch["tokens"], batch["targets"], mask,
                    prefix_embeds=batch.get("prefix"), num_valid=num_valid,
                    counters=True)
            # the aux term is differentiated but reported separately: the
            # metas carry the plain SUM loss
            total = (ls + aux_weight * aux * jnp.maximum(ws, 1.0)
                     if aux_weight else ls)
            if counters:
                # the model's counters ride beside the aux loss
                aux = {"aux_loss": aux, **counters}
            return total, (ls, ws, aux)

        (_, metas), g = jax.value_and_grad(lf, has_aux=True)(params)
        return metas, g

    return Workload(
        name=getattr(model_cfg, "name", model_cfg.family),
        init=lambda key: init(key, model_cfg),
        loss_and_grad=loss_and_grad,
        next_batch=pipe.next_batch,
        state_dict=pipe.state_dict,
        load_state_dict=pipe.load_state_dict,
    )
