"""Whether a cell's largest worker step fits one chip, by the compiler's own
count, with no chip attached: the workload's gradient function at the
largest bucket the controller can reach, compiled for a described TPU.

    python3 bench/fit.py --workload <cell> [--layers <n>] [--topology v5e:2x2]

Prints one JSON line per compile: the bucket, and the compiled program's
argument, output and temporary bytes, or the compiler's refusal ("Used
<x>G of <y>G hbm").  Benchmark runs never run this; it sizes cells.
"""

from __future__ import annotations

import argparse
import json
import re
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def param_structs(arch, conf: dict, sharding=None):
    """The architecture module's parameter tree as float32 shapes, the
    tree ``make_params`` makes, without making it."""
    import jax
    import jax.numpy as jnp

    return jax.tree.map(
        lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                           sharding=sharding),
        arch.param_shapes(conf), is_leaf=lambda x: isinstance(x, tuple))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--layers", type=int, default=None,
                    help="num_hidden_layers in place of the file's")
    ap.add_argument("--topology", default="v5e:2x2")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import harness, program
    from repro.api import lm_workload

    jax.config.update("jax_enable_compilation_cache", False)
    spec = harness.load_cell(ROOT, args.workload)
    conf, traffic, arch = dict(spec["config"]), spec["traffic"], spec["arch"]
    if args.layers is not None:
        conf["num_hidden_layers"] = args.layers
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name=args.topology)
    sh = SingleDeviceSharding(topo.devices[0])
    wl = lm_workload(arch.program_config(conf), program._FeedSource(None),
                     use_kernel=True)

    def struct(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    params = param_structs(arch, conf, sh)
    n, s = max(harness.reachable_batches(traffic)), traffic["seq_len"]
    data = {"tokens": struct((n, s), jnp.int32),
            "targets": struct((n, s), jnp.int32)}
    if conf.get("num_image_tokens"):
        data["prefix"] = struct((n, conf["num_image_tokens"],
                                 conf["hidden_size"]))
    out = {"workload": args.workload, "layers": conf["num_hidden_layers"],
           "bucket": n, "topology": args.topology}
    try:
        m = jax.jit(wl.loss_and_grad).lower(
            params, data, struct((n,))).compile().memory_analysis()
        out.update(argument_bytes=m.argument_size_in_bytes,
                   output_bytes=m.output_size_in_bytes,
                   temp_bytes=m.temp_size_in_bytes)
    except Exception as e:  # the compiler's refusal is the answer
        found = re.search(r"Used [0-9.]+G of [0-9.]+G hbm", str(e))
        if found is None:
            raise
        out["refused"] = found.group(0)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
