"""Subprocess helper: verify the shard_map decode-attention path produces
the same logits as the unsharded fallback, on an 8-fake-device mesh."""

import os
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.configs import get_config  # noqa: E402
from repro.launch.mesh import make_debug_mesh  # noqa: E402
from repro.models import apply_lm, init_caches, init_lm, reduced  # noqa: E402
from repro.models import shard_hooks  # noqa: E402


def run(arch: str) -> int:
    cfg = reduced(get_config(arch))
    if cfg.attention == "mla":
        # ranks divisible by the 2-way model axis, rope pairs intact
        cfg = cfg.with_(kv_lora_rank=16, qk_rope_dim=8)
    if cfg.num_experts:
        cfg = cfg.with_(moe_capacity_factor=8.0)
    b, s = 4, 8
    key = jax.random.PRNGKey(0)
    params = init_lm(key, cfg)
    toks = jax.random.randint(key, (b, s), 0, cfg.vocab_size)

    def decode_all():
        caches = init_caches(cfg, b, s)
        outs = []
        for i in range(s):
            lg, caches, _ = apply_lm(
                params, cfg, toks[:, i:i + 1], caches=caches,
                positions=jnp.full((b, 1), i, jnp.int32))
            outs.append(lg)
        return jnp.concatenate(outs, axis=1)

    plain = decode_all()

    mesh = make_debug_mesh(4)
    shard_hooks.set_rules({"decode_attn": (mesh, ("data",), "model")})
    try:
        with mesh:
            sharded = decode_all()
    finally:
        shard_hooks.set_rules(None)

    err = float(jnp.max(jnp.abs(plain - sharded)))
    rel = err / (float(jnp.max(jnp.abs(plain))) + 1e-9)
    assert rel < 2e-3, f"{arch}: shard_map decode diverges rel={rel}"
    print(f"OK {arch} shard_map decode rel_err={rel:.2e}")
    return 0


if __name__ == "__main__":
    sys.exit(run(sys.argv[1] if len(sys.argv) > 1 else "llama3-8b"))
