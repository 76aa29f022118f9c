"""Subprocess helper: run a REAL (allocating) sharded train step on a small
fake-device mesh. Executed by test_sharding.py in a fresh interpreter so the
XLA device-count flag can be set before jax initializes."""

import os
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.configs import get_config  # noqa: E402
from repro.launch import sharding as SH  # noqa: E402
from repro.launch import steps as ST  # noqa: E402
from repro.launch.mesh import make_debug_mesh  # noqa: E402
from repro.models import init_lm, reduced  # noqa: E402
from repro.models import shard_hooks  # noqa: E402
from repro.optim import adam  # noqa: E402


def main(arch: str) -> int:
    cfg = reduced(get_config(arch)).with_(
        d_model=128, num_heads=4, num_kv_heads=2, head_dim=32,
        vocab_size=512)
    if cfg.family == "hybrid":
        cfg = cfg.with_(num_heads=2, num_kv_heads=1, head_dim=64,
                        lru_width=128)
    if cfg.attention == "mla":
        cfg = cfg.with_(num_heads=4, head_dim=0)
    mesh = make_debug_mesh(8)
    shard_hooks.set_rules({
        "logits": NamedSharding(mesh, P("data", None, "model")),
        "activations": NamedSharding(mesh, P("data", None, None)),
    })

    params = init_lm(jax.random.PRNGKey(0), cfg)
    p_shard = SH.params_shardings(params, mesh, fsdp=True)
    params = jax.device_put(params, p_shard)
    opt = adam(1e-3)
    opt_state = opt.init(params)
    o_shard = SH.opt_state_shardings(
        jax.eval_shape(lambda: opt_state), params, p_shard, mesh)
    opt_state = jax.device_put(opt_state, o_shard)

    b, s = 8, 16
    batch = {
        "tokens": jnp.zeros((b, s), jnp.int32),
        "targets": jnp.ones((b, s), jnp.int32),
        # variable-batch weights: only 6 of 8 examples active (b_k masking)
        "weights": jnp.asarray([1, 1, 1, 1, 1, 1, 0, 0], jnp.float32),
    }
    batch = jax.device_put(batch, SH.batch_shardings(batch, mesh))

    metrics_shard = {k: NamedSharding(mesh, P())
                     for k in ("loss", "aux", "weight_sum")}
    step_fn = jax.jit(ST.make_train_step(cfg, opt),
                      in_shardings=(p_shard, o_shard,
                                    NamedSharding(mesh, P()),
                                    SH.batch_shardings(batch, mesh)),
                      # params/opt feed back into the next step: outputs must
                      # keep the input shardings (training-loop invariant)
                      out_shardings=(p_shard, o_shard, metrics_shard),
                      donate_argnums=(0, 1))
    with mesh:
        params2, opt_state2, metrics = step_fn(
            params, opt_state, jnp.zeros((), jnp.int32), batch)
        loss1 = float(metrics["loss"])
        params3, _, metrics2 = step_fn(params2, opt_state2,
                                       jnp.ones((), jnp.int32), batch)
        loss2 = float(metrics2["loss"])

    assert jnp.isfinite(loss1) and jnp.isfinite(loss2), (loss1, loss2)
    assert loss2 < loss1, f"loss did not decrease: {loss1} -> {loss2}"
    assert float(metrics["weight_sum"]) == 6 * s, metrics["weight_sum"]
    print(f"OK {arch} loss {loss1:.4f} -> {loss2:.4f}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1] if len(sys.argv) > 1 else "llama3-8b"))
