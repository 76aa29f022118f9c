"""Smoke run of the heterogeneous dynamic-batching trainer on TPU chips.

    python3 chip_smoke.py              # one chip: Yi-9B widths, 5 BSP rounds
    python3 chip_smoke.py --chips 4    # four chips: 3 workers on 2+1+1 slices,
                                       # concurrent against sequential dispatch

One process, no subprocesses.  It refuses to run where JAX finds no TPU:
nothing falls back to the CPU.  Every check must pass before it prints its
last line, one JSON object naming the device:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

The model is Yi-9B (``configs/yi_9b.py``) at its published widths: d_model
4096, 32 query and 4 KV heads of 128, SwiGLU d_ff 11008, rope theta 10000.
It is cut three ways, and each cut is printed before training starts:

  * one decoder layer, one whole period of a dense model: the one-chip run
    time-multiplexes 3 workers and keeps their 3 gradients alive through
    the optimizer update, about 10 float32 copies of the parameters, and
    two layers (412M parameters) would not fit 16 GB;
  * an eighth of the vocabulary (8000 ids), one chip's share where the
    vocabulary is split over 8 chips; the data pipeline draws its ids from
    that slice;
  * random weights from ``--seed``.

Training goes through the normal front door (``repro.api.Experiment`` with
a ``MeshBackend``) at the ``train_4k`` sequence length (configs/shapes.py),
with the ragged Pallas flash-attention kernel on the attention path.
"""

from __future__ import annotations

import argparse
import functools
import gc
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.api import (  # noqa: E402
    ClusterSpec,
    Experiment,
    Hook,
    MeshBackend,
    TrainConfig,
    lm_workload,
)
from repro.configs import get_config  # noqa: E402
from repro.configs.shapes import get_shape  # noqa: E402
from repro.data import DataPipeline  # noqa: E402
from repro.kernels.flash_attention.ops import attention  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_data_mesh  # noqa: E402
from repro.models import lm_loss  # noqa: E402
from repro.optim import adam  # noqa: E402
from repro.train import spans  # noqa: E402

ARCH = "yi-9b"
VOCAB_SHARE = 8          # the vocabulary is split over 8 chips
WORKERS = 3
LR = 1e-3
STEPS = 5                # BSP rounds per run

# Round-0 loss of the kernel path (XLA matmuls at the TPU's default
# precision, the kernel in float32) against the float32 reference under
# "highest": they agreed to 7.2e-7 relative on a v5e chip (3.3e-8 with the
# kernel's dots in bfloat16), and 1e-5 leaves room for summation order.
# Rows of 4096 random tokens differ in mean loss by far more, so a wrong
# row, a counted padding row or other parameters miss the bound.  Matmul
# precision does not: at random initialisation bfloat16 matmuls moved this
# loss by 1.3e-7 on the same chip, so precision is the kernel check's job.
LOSS_RTOL = 1e-5
# Kernel against reference, forward output and gradients, each as its
# largest absolute error over the reference's largest magnitude, at seq
# 2048 on a v5e chip.  With float32 MXU passes the kernel read 3.5e-7
# (out) to 3.5e-5 (dq); with its dots at Mosaic's default precision (one
# bfloat16 pass) it read 2.4e-3 to 5.4e-3.  The bound sits near the
# geometric middle, about 8x from each, so the bfloat16 path fails; a
# dropped head, a wrong mask or a misplaced block is of order one.
KERNEL_TOL = 3e-4
# Four chips, concurrent against sequential dispatch: the same rows, weights
# and kernel; only the order of sums inside and across chips differs.
# Losses agreed to 3.4e-6 and 4.3e-6 relative over 5 rounds in two runs on
# a v5e host.  Parameters are compared by how far apart the two legs ended,
# over how far training moved them: Adam turns rounding-level gradient
# differences into steps of up to the learning rate, and the legs ended
# 1.1e-2 and 1.3e-2 apart.  A planted fault on the same host (worker 2's
# gradient dropped in every concurrent round) put the losses 3.7e-2 and
# the parameters 0.73 apart, so each bound alone catches it.
FOUR_CHIP_LOSS_RTOL = 1e-4
FOUR_CHIP_PARAM_RTOL = 5e-2


def smoke_config():
    """Yi-9B at published widths with the depth and vocabulary cuts."""
    cfg = get_config(ARCH)
    return cfg.with_(num_layers=1, vocab_size=cfg.vocab_size // VOCAB_SHARE)


def emit(tag: str, **fields) -> None:
    print(f"{tag} {json.dumps(fields, default=str)}", flush=True)


class SmokeFailure(Exception):
    """A check of the smoke run did not hold."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# --------------------------------------------------------------- measuring


def compiles_since(start: tuple[int, float]) -> tuple[int, float]:
    """Backend compiles (persistent-cache loads included) and their seconds
    since ``start``, an earlier ``spans.compiles()``."""
    n, s = spans.compiles()
    return n - start[0], s - start[1]


class RoundLog(Hook):
    """Prints every round and keeps what the round-0 reference needs: the
    parameters and batch sizes going in, and the batches fetched in it."""

    def __init__(self, next_batch):
        self._next_batch = next_batch
        self._fetched: list[dict] = []
        self.params0 = None
        self.sizes0 = None
        self.round0 = None
        self.losses: list[float] = []

    def next_batch(self, worker, n):
        batch = self._next_batch(worker, n)
        if self.round0 is None:
            self._fetched.append(batch)
        return batch

    def on_run_start(self, session):
        self.params0 = session.params
        self.sizes0 = session.batches
        self._fetched = []          # drop the probe round's batches

    def on_step(self, session, rec):
        if rec.step == 0:
            self.round0, self._fetched = self._fetched, []
        self.losses.append(rec.loss)
        emit("round", step=rec.step, loss=rec.loss,
             worker_seconds=rec.worker_times, next_batches=rec.batches,
             round_seconds=rec.iteration_time)


# ------------------------------------------------------------------ phases


def train(cfg, *, seq_len: int, seed: int, backend, steps: int = STEPS,
          b0: int = 2, microbatch: int = 1, batching: str = "dynamic"):
    """Train through ``repro.api`` with the kernel on.

    Returns ``(trainer, summary, log)``: the built trainer, the session's
    summary dict and the :class:`RoundLog` that watched it."""
    pipe = DataPipeline(cfg, seq_len=seq_len, num_workers=WORKERS, seed=seed)
    workload = lm_workload(cfg, pipe, use_kernel=True)
    log = RoundLog(workload.next_batch)
    workload.next_batch = log.next_batch
    experiment = Experiment(
        workload=workload,
        cluster=ClusterSpec.hlevel(39, 6, WORKERS, workload="transformer",
                                   backend=backend),
        optimizer=adam(LR),
        config=TrainConfig(b0=b0, microbatch=microbatch, batching=batching,
                           max_steps=steps, seed=seed))
    session = experiment.session(hooks=[log])
    out = session.run()
    return session.trainer, out, log


def reference_loss(cfg, params, batches, sizes) -> float:
    """Round-0 loss of the plain float32 model (no kernel, "highest" matmul
    precision), one row at a time so that the dense (S, S) attention scores
    fit."""
    row_loss = jax.jit(lambda p, t, y: lm_loss(
        p, cfg, t, y, jnp.ones((1,), jnp.float32))[:2])
    total = weight = 0.0
    with jax.default_matmul_precision("highest"):
        for batch, n in zip(batches, sizes):
            for r in range(n):
                ls, ws = row_loss(params, batch["tokens"][r:r + 1],
                                  batch["targets"][r:r + 1])
                total += float(ls)
                weight += float(ws)
    return total / weight


def worker_step_compiled(trainer, seq_len: int, worker: int = 0):
    """Worker ``worker``'s compiled gradient step at its largest bucket."""
    rows = jax.ShapeDtypeStruct(
        (max(trainer.worker_buckets[worker]), seq_len), jnp.int32)
    return trainer.compiled_step(worker, {"tokens": rows, "targets": rows})


def kernel_errors(cfg, *, seq_len: int, seed: int) -> dict:
    """Kernel against the float32 reference at the model's head geometry:
    a 2-row batch with 1 valid row, forward output and the three input
    gradients.  The reference sees only the valid row; the padded row must
    come back as exact zeros."""
    b, h, hkv, d = 2, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (b, seq_len, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, seq_len, hkv, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, seq_len, hkv, d), jnp.float32)
    w = jax.random.normal(ks[3], (b, seq_len, h, d), jnp.float32)

    # the inputs are arguments, not closed-over constants, so they are not
    # baked into the compiled programs
    @functools.partial(jax.jit, static_argnames="use_kernel")
    def run(q, k, v, w, use_kernel):
        def loss(q_, k_, v_):
            out = attention(q_, k_, v_, num_valid=jnp.int32(1),
                            use_kernel=use_kernel,
                            interpret=jax.default_backend() == "cpu")
            return (out * w).sum(), out
        (_, out), grads = jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
        return out, grads

    out_k, grads_k = run(q, k, v, w, use_kernel=True)
    with jax.default_matmul_precision("highest"):
        out_r, grads_r = run(q[:1], k[:1], v[:1], w[:1], use_kernel=False)
    errors = {}
    for name, xk, xr in zip(("out", "dq", "dk", "dv"),
                            (out_k, *grads_k), (out_r, *grads_r)):
        xk, xr = np.asarray(xk), np.asarray(xr)
        check(not np.any(xk[1:]), f"padded row of {name} is not zero")
        errors[name] = float(np.max(np.abs(xk[:1] - xr))
                             / np.max(np.abs(xr)))
    return errors


def peak_bytes(devices) -> list:
    """``peak_bytes_in_use`` of each device, where the backend reports it."""
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in devices]


# -------------------------------------------------------------------- runs


def print_cuts(cfg, seed: int) -> None:
    emit("cut", what="depth", num_layers=cfg.num_layers, published=48,
         why="one whole period of a dense model; the 3 worker gradients "
             "kept through the Adam update leave no room for two layers")
    emit("cut", what="vocabulary", vocab_size=cfg.vocab_size,
         published=VOCAB_SHARE * cfg.vocab_size,
         why=f"one chip's share of a vocabulary split over {VOCAB_SHARE} "
             f"chips; the data draws its ids from that slice")
    emit("cut", what="weights", init="random", seed=seed)


def one_chip(cfg, *, seq_len: int, seed: int) -> None:
    """3 time-multiplexed workers on one chip, dynamic batching,
    heterogeneity from the cluster spec's declared speeds."""
    start = spans.compiles()
    trainer, out, log = train(cfg, seq_len=seq_len, seed=seed,
                              backend=MeshBackend(dilation="from-spec"))
    compiles, compile_s = compiles_since(start)
    emit("train", rounds=out["steps"], wall_seconds=out["wall_time"],
         compiles=compiles, compile_seconds=compile_s,
         worker_traces=trainer.accum_traces,
         buckets=[sorted(b) for b in trainer.worker_buckets],
         peak_bytes_in_use=peak_bytes(jax.devices()[:1]))
    check(out["steps"] == STEPS, f"{out['steps']} of {STEPS} rounds ran")
    check(bool(np.all(np.isfinite(log.losses))),
          f"non-finite loss in {log.losses}")

    compiled = worker_step_compiled(trainer, seq_len)
    calls = compiled.as_text().count("tpu_custom_call")
    emit("worker_step", tpu_custom_calls=calls,
         memory=str(compiled.memory_analysis()))
    check(calls > 0, "the worker step holds no Pallas kernel")

    del trainer, compiled
    gc.collect()    # the trainer's jitted closures hold it in a cycle
    ref = reference_loss(cfg, log.params0, log.round0, log.sizes0)
    rel = abs(log.losses[0] - ref) / abs(ref)
    emit("reference", round0_loss=log.losses[0], reference_loss=ref,
         rel_diff=rel, rtol=LOSS_RTOL)
    check(rel <= LOSS_RTOL, f"round-0 loss {log.losses[0]} is {rel:.2e} "
                            f"from the float32 reference {ref}")

    del log
    gc.collect()
    errors = kernel_errors(cfg, seq_len=seq_len // 2, seed=seed)
    emit("kernel_check", tol=KERNEL_TOL, **errors)
    check(max(errors.values()) <= KERNEL_TOL,
          f"kernel against reference {errors}")


def four_chip(cfg, *, seq_len: int, seed: int) -> None:
    """3 workers on 2+1+1 slices of four chips with real, undilated timings,
    concurrent dispatch against sequential time-multiplexing of the same
    chips.  Uniform batches keep both legs on identical rows (a controller
    fed two legs' different timings would split them differently)."""
    devices = jax.devices()
    check(len(devices) >= 4, f"--chips 4 needs 4 devices, JAX has "
                             f"{len(devices)}")
    mesh = make_data_mesh(4)
    runs = {}
    for concurrent in (True, False):
        leg = "concurrent" if concurrent else "sequential"
        start = spans.compiles()
        trainer, out, log = train(
            cfg, seq_len=seq_len, seed=seed,
            backend=MeshBackend(mesh=mesh, concurrent=concurrent),
            b0=4, microbatch=4, batching="uniform")
        compiles, compile_s = compiles_since(start)
        check(bool(np.all(np.isfinite(log.losses))),
              f"{leg}: non-finite loss in {log.losses}")
        emit(leg, rounds=out["steps"], wall_seconds=out["wall_time"],
             compiles=compiles, compile_seconds=compile_s,
             slices=(trainer.slice_plan.slices
                     if trainer.slice_plan is not None else None),
             last_round_stamps=trainer.last_round_stamps,
             peak_bytes_in_use=peak_bytes(devices[:4]))
        if concurrent:
            check_slices(trainer)
            params0 = jax.device_get(log.params0)
        runs[leg] = (log.losses, jax.device_get(trainer.params))
        del trainer, log
        gc.collect()

    (loss_c, params_c), (loss_s, params_s) = runs["concurrent"], \
        runs["sequential"]
    loss_rel = max(abs(a - b) / abs(b) for a, b in zip(loss_c, loss_s))
    apart = moved = 0.0
    for c, q, p0 in zip(*map(jax.tree.leaves, (params_c, params_s, params0))):
        apart += float(np.sum(np.square(c - q)))
        moved += float(np.sum(np.square(q - p0)))
    param_rel = (apart / moved) ** 0.5
    emit("agreement", loss_rel_diff=loss_rel, loss_rtol=FOUR_CHIP_LOSS_RTOL,
         param_rel_diff=param_rel, param_rtol=FOUR_CHIP_PARAM_RTOL)
    check(loss_rel <= FOUR_CHIP_LOSS_RTOL,
          f"per-round losses differ by {loss_rel:.2e} relative")
    check(param_rel <= FOUR_CHIP_PARAM_RTOL,
          f"final parameters differ by {param_rel:.2e} of the distance "
          f"training moved them")


def check_slices(trainer) -> None:
    """Every worker on its own slice, all of the last round's calls in
    flight at once, and each slice's outputs of that round on that slice's
    devices (not all on the first chip)."""
    slices = trainer.slice_plan.slices
    check(trainer.concurrent and slices == ((0, 2), (2, 1), (3, 1)),
          f"expected 2+1+1 concurrent slices, got {slices}")
    stamps = trainer.last_round_stamps
    check(max(t0 for t0, _ in stamps) < min(done for _, done in stamps),
          f"the last round's calls were not all in flight at once: {stamps}")
    for worker, (placed, (start, length)) in enumerate(
            zip(trainer.last_round_devices, slices)):
        want = frozenset(trainer.slice_devices(start, length))
        check(placed == {want}, f"worker {worker}'s outputs are on "
                                f"{placed}, its slice is {want}")
    emit("slice_devices", devices=[
        sorted(str(x) for s in placed for x in s)
        for placed in trainer.last_round_devices])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sliced four-chip path and its "
                         "sequential comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              f"nothing runs on the CPU", file=sys.stderr)
        return 2
    emit("setup", compile_cache=enable_compile_cache(),
         device_kind=dev.device_kind, device_count=len(jax.devices()),
         jax=jax.__version__)
    cfg = smoke_config()
    print_cuts(cfg, args.seed)
    try:
        (four_chip if args.chips == 4 else one_chip)(
            cfg, seq_len=get_shape("train_4k").seq_len, seed=args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
