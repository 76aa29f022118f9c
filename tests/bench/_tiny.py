"""A benchmark root at a size a CPU test can run: the benchmark's own files
copied into a temporary directory, plus tiny configurations, a tiny
traffic mix, limits and a ``BENCHMARK.json`` that names them."""

from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

TINY_DENSE = {
    "name": "tiny-dense", "source": "test", "model": "decoder",
    "hidden_size": 64, "intermediate_size": 128,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
    "num_hidden_layers": 1, "vocab_size": 256, "rope_theta": 10000.0,
    "rms_norm_eps": 1e-5, "tie_word_embeddings": False,
    "program": {"arch": "yi-9b", "family": "dense"},
}
TINY_VLM = dict(TINY_DENSE, name="tiny-vlm", num_key_value_heads=4,
                num_image_tokens=8,
                program={"arch": "phi-3-vision-4.2b", "family": "vlm"})
TINY_TRAFFIC = {
    "seq_len": 128, "workers": 3,
    "cluster": {"kind": "hlevel", "total_cores": 39, "h_level": 6,
                "sim_workload": "transformer"},
    "devices": 1, "concurrent": False, "dilation": "from-spec",
    "batching": "dynamic", "b0": 2, "microbatch": 1,
    "controller": {"kind": "p", "b_min": 1, "b_max": 3},
    "optimizer": {"name": "adam", "lr": 0.001, "b1": 0.9, "b2": 0.999,
                  "eps": 1e-8},
    "checked_steps": 3,
}
# on the CPU the program's matmuls and the kernel (interpret mode) run in
# float32, so only the order of sums differs from the reference
TINY_LIMITS = {"loss_gap.1": 1e-5, "loss_gap.2": 1e-5, "loss_gap.3": 1e-5,
               "grad_gap": 1e-4, "delta_gap": 1e-2}


def write_json(path: Path, obj) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(obj, indent=1))


def tiny_root(tmp: Path) -> Path:
    """A copy of ``bench/`` with two tiny cells, ``tiny.dense`` and
    ``tiny.vlm``, added as files."""
    root = tmp / "root"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = []
    for conf in (TINY_DENSE, TINY_VLM):
        write_json(root / "bench" / "configs" / f"{conf['name']}.json", conf)
        spec["configs"].append({
            "name": conf["name"], "source": "test",
            "file": f"bench/configs/{conf['name']}.json", "reduced": [],
            "why": "test"})
        name = f"tiny.{conf['program']['family']}"
        cells.append({"name": name, "config": conf["name"],
                      "traffic": "tiny-het3", "chips": 1, "why": "test"})
        write_json(root / "bench" / "limits" / f"{name}.json",
                   {"limits": TINY_LIMITS})
    spec["workloads"] += cells
    write_json(root / "bench" / "traffic" / "tiny-het3.json", TINY_TRAFFIC)
    write_json(root / "BENCHMARK.json", spec)
    return root
