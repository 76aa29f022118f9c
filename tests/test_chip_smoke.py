"""chip_smoke.py on the CPU: its train phase at a reduced size with the
kernel in interpret mode, its refusal to run without a TPU, and the compile
cache placement its entry points share."""

import os
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402
from repro.api import MeshBackend  # noqa: E402
from repro.launch import compile_cache  # noqa: E402
from repro.models import reduced  # noqa: E402


def test_train_phase_matches_reference_at_reduced_size():
    """The smoke's own train phase (Experiment -> MeshTrainer ->
    lm_workload with the kernel) on reduced Yi-9B: finite losses over
    every round, and a round-0 loss equal to the plain float32 reference
    on the same parameters and rows (interpret mode and XLA:CPU compute
    in float32, so only summation order differs)."""
    cfg = reduced(chip_smoke.smoke_config())
    trainer, out, log = chip_smoke.train(
        cfg, seq_len=128, steps=3, seed=0,
        backend=MeshBackend(dilation="from-spec"))
    assert out["steps"] == 3
    assert len(log.losses) == 3 and np.all(np.isfinite(log.losses))
    assert sum(log.sizes0) == chip_smoke.WORKERS * 2
    assert len(log.round0) == chip_smoke.WORKERS
    ref = chip_smoke.reference_loss(cfg, log.params0, log.round0,
                                    log.sizes0)
    assert log.losses[0] == pytest.approx(ref, rel=1e-5)


def test_kernel_check_at_reduced_size():
    """The smoke's kernel check on reduced Yi-9B heads in interpret mode:
    padded rows come back as zeros (checked inside) and the kernel matches
    the reference far inside the chip's bound, since interpret mode
    computes its dots in float32."""
    cfg = reduced(chip_smoke.smoke_config())
    errors = chip_smoke.kernel_errors(cfg, seq_len=256, seed=0)
    assert set(errors) == {"out", "dq", "dk", "dv"}
    assert max(errors.values()) < 1e-4


def test_main_refuses_a_host_without_tpu(capsys):
    assert jax.devices()[0].platform == "cpu"
    assert chip_smoke.main([]) != 0
    captured = capsys.readouterr()
    assert '"ok"' not in captured.out
    assert "no TPU" in captured.err


def test_compile_cache_dir_is_fixed(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == before

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        first = compile_cache.enable_compile_cache()
        assert compile_cache.enable_compile_cache() == first
        assert jax.config.jax_compilation_cache_dir == first
        assert os.path.realpath(first) == os.path.realpath(
            os.path.join(ROOT, ".jax_cache"))
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
