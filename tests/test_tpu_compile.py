"""Compile the main-path Pallas kernel for a described TPU v5e chip.

Interpret mode runs a kernel's body on the CPU and never asks the chip's
compiler (Mosaic) whether its blocks tile or fit in VMEM; these tests do,
with no chip attached.  The flash-attention forward on the ragged grid,
and forward plus backward through ``ops.attention``, at Yi-9B's head
geometry and the smoke run's shapes (configs/yi_9b.py, chip_smoke.py): a
4-row bucket of 4096 tokens, 32 query heads and 4 KV heads of 128.  The
forward plus backward also compiles at the largest bucket of each
benchmark cell, in float32 with the tiles ``tile_plan`` chooses: Phi-3's
7 rows of 32 MHA heads of 96, Yi-9B's 6 rows of 32/4 heads of 128, and
Mellum2's 4 rows of 32/4 heads of 128 windowed to 1024.  The experts'
grouped matmul (megablox ``gmm``/``tgmm``) compiles forward and backward
at Mellum2's widths and token group.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports every test file.  The persistent compilation cache is off around
these compiles, since an entry written for a described chip cannot be read
back without one.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import flash_attention
from repro.kernels.flash_attention.ops import attention

B, S, H, HKV, D = 4, 4096, 32, 4, 128


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


def _inputs(sharding, dtype, b=B, h=H, hkv=HKV, d=D):
    q = jax.ShapeDtypeStruct((b, S, h, d), dtype, sharding=sharding)
    kv = jax.ShapeDtypeStruct((b, S, hkv, d), dtype, sharding=sharding)
    nv = jax.ShapeDtypeStruct((), jnp.int32, sharding=sharding)
    return q, kv, kv, nv


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ragged_forward_compiles(one_chip, dtype):
    def fwd(q, k, v, nv):
        return flash_attention(q, k, v, num_valid=nv, ragged_impl="grid")

    compiled = jax.jit(fwd).lower(*_inputs(one_chip, dtype)).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1


# (dtype, rows, q heads, kv heads, head_dim)
FWD_BWD_CASES = [
    pytest.param(jnp.float32, B, H, HKV, D, id="float32"),
    pytest.param(jnp.bfloat16, B, H, HKV, D, id="bfloat16"),
    pytest.param(jnp.float32, 7, 32, 32, 96, id="phi3v-cell-float32"),
    pytest.param(jnp.float32, 6, 32, 4, 128, id="yi9b-cell-float32"),
]


@pytest.mark.parametrize("dtype, b, h, hkv, d", FWD_BWD_CASES)
def test_forward_and_backward_compile(one_chip, dtype, b, h, hkv, d):
    def grads(q, k, v, nv):
        def loss(q_, k_, v_):
            out = attention(q_, k_, v_, num_valid=nv)
            return out.astype(jnp.float32).sum()
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    compiled = jax.jit(grads).lower(
        *_inputs(one_chip, dtype, b, h, hkv, d)).compile()
    # the forward, the dq kernel and the dk/dv kernel
    assert compiled.as_text().count("tpu_custom_call") == 3


def test_windowed_forward_and_backward_compile(one_chip):
    """Mellum2's sliding layers: the band of tiles a 1024 window keeps."""
    def grads(q, k, v, nv):
        def loss(q_, k_, v_):
            out = attention(q_, k_, v_, num_valid=nv, window=1024)
            return out.sum()
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    compiled = jax.jit(grads).lower(
        *_inputs(one_chip, jnp.float32, 4, 32, 4, 128)).compile()
    assert compiled.as_text().count("tpu_custom_call") == 3


def _held_layer(one_chip, monkeypatch, held=8, rungs=None):
    """Mellum2's held-expert layer at its widths over one 4096-token group
    (``held`` of 64 experts of 896, d 2304), forward and backward,
    compiled with the kernel on (the layer asks ``jax.default_backend()``
    whether to interpret it, so the answer is "tpu"); ``rungs`` in place
    of the layer's ladder."""
    from repro.configs import get_config
    from repro.models import layers as L

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    if rungs is not None:
        monkeypatch.setattr(L, "held_rungs", lambda *a: rungs)
    cfg = get_config("mellum2-12b-a2.5b").with_(experts_held=held,
                                                use_pallas=True)
    d, f, e = cfg.d_model, cfg.moe_d_ff, cfg.num_experts

    def struct(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    params = {"router": {"w": struct((d, e))},
              "w_gate": struct((held, d, f)), "w_up": struct((held, d, f)),
              "w_down": struct((held, f, d))}

    def grads(p, x):
        return jax.value_and_grad(
            lambda p_, x_: L.apply_held_moe(p_, x_, cfg)[0].sum(),
            argnums=(0, 1))(p, x)

    return jax.jit(grads).lower(params, struct((1, 4096, d))).compile()


def _kernel_calls(compiled):
    return sorted(line.split(" = ", 1)[0].strip().lstrip("%").split(".")[0]
                  for line in compiled.as_text().splitlines()
                  if "tpu_custom_call" in line and " = " in line)


def test_grouped_matmul_compiles(one_chip, monkeypatch):
    """With 8 of 64 experts held a group's pairs take one of three buffer
    sizes (8,192, 16,384 or 32,768 rows), each compiled: per size the
    forward, recomputed and input-gradient products are ``gmm`` calls,
    the weight gradients ``tgmm``, the stems the benchmark's expert
    readers time."""
    calls = _kernel_calls(_held_layer(one_chip, monkeypatch))
    assert calls == ["gmm"] * 9 * 3 + ["tgmm"] * 3 * 3


def test_grouped_matmul_one_rung_with_every_expert_held(one_chip,
                                                        monkeypatch):
    """With all 64 experts held the layer has one buffer size, the most
    a group can fill: one set of kernels and no conditional."""
    compiled = _held_layer(one_chip, monkeypatch, held=64)
    assert _kernel_calls(compiled) == ["gmm"] * 9 + ["tgmm"] * 3
    assert " conditional(" not in compiled.as_text()


def test_compact_rung_needs_fewer_temporaries(one_chip, monkeypatch):
    """By the compiler's count: the smallest buffer (8,192 rows) needs
    fewer temporary bytes than the 32,768-row one every group used to
    take, and the whole ladder no more than those two together, which a
    backward keeping every size's residuals (JAX's own linearisation of
    the ``switch``, unused ones zero-filled) would exceed."""
    def temps(rungs):
        return _held_layer(one_chip, monkeypatch, rungs=rungs
                           ).memory_analysis().temp_size_in_bytes

    small, full = temps((8192,)), temps((32768,))
    assert small < full
    assert temps(None) < small + full
