"""Operations, bytes and peaks the benchmark's metrics divide by: hand
counts for both configurations, kernel work that ignores the lane pad,
and a peaks table that refuses an unknown chip."""

from __future__ import annotations

import json
import math

import pytest

from _tiny import ROOT

from bench import peaks, work  # noqa: E402
from bench.inputs import param_shapes  # noqa: E402

YI = json.loads((ROOT / "bench" / "configs" / "yi9b-1l.json").read_text())
PHI = json.loads((ROOT / "bench" / "configs" / "phi3v-1l.json").read_text())
SEQ = 4096
PAIRS = SEQ * (SEQ + 1) // 2


def test_hand_counts_yi():
    layer = 2 * 4096 * 4096 + 2 * 4096 * 512 + 3 * 4096 * 11008
    assert work.layer_matmul_params(YI) == layer == 173_015_040
    assert work.param_count(YI) == layer + 2 * 4096 * 8000 + 3 * 4096
    assert round(work.param_count(YI) / 1e6, 1) == 238.6
    attn = 12 * 128 * 32 * PAIRS / SEQ
    flops = work.train_flops_per_position(YI, SEQ)
    assert flops == pytest.approx(6 * (layer + 4096 * 8000) + attn)
    assert round(flops / 1e9, 2) == 1.34


def test_hand_counts_phi():
    layer = 4 * 3072 * 3072 + 3 * 3072 * 8192
    assert work.layer_matmul_params(PHI) == layer == 113_246_208
    assert round(work.param_count(PHI) / 1e6, 1) == 137.9
    flops = work.train_flops_per_position(PHI, SEQ)
    assert flops == pytest.approx(6 * (layer + 3072 * 4008)
                                  + 12 * 96 * 32 * PAIRS / SEQ)
    assert round(flops / 1e9, 2) == 0.83


@pytest.mark.parametrize("conf", [YI, PHI])
def test_param_count_is_the_weights_made(conf):
    shapes = param_shapes(conf)
    sizes = []

    def walk(t):
        for v in t.values():
            walk(v) if isinstance(v, dict) else sizes.append(math.prod(v))

    walk(shapes)
    assert sum(sizes) == work.param_count(conf)


def test_flash_work_uses_the_published_head_dim():
    """Phi's head_dim 96 is padded to 128 lanes inside the kernel; the
    work counted is the 96 the algorithm needs, whatever the pad."""
    flops, nbytes = work.flash_work_per_row(PHI, SEQ)
    assert flops == 12 * 96 * 32 * PAIRS
    padded = dict(PHI, head_dim=128)
    assert work.flash_work_per_row(padded, SEQ)[0] == flops * 128 / 96
    q = SEQ * 32 * 96 * 4
    lse = SEQ * 32 * 4
    # forward: q, k, v in, o and lse out; backward: q, k, v, o, dO, lse
    # in, dq, dk, dv out (MHA: k and v as wide as q)
    assert nbytes == (3 * q + q + lse) + (5 * q + lse + 3 * q)
    f3, b3 = work.flash_work(PHI, SEQ, 3)
    assert (f3, b3) == (3 * flops, 3 * nbytes)


def test_gqa_kernel_bytes_count_kv_heads():
    _, nbytes = work.flash_work_per_row(YI, SEQ)
    q, kv, lse = SEQ * 32 * 128 * 4, SEQ * 4 * 128 * 4, SEQ * 32 * 4
    assert nbytes == (q + 2 * kv + q + lse) + (3 * q + 2 * kv + lse
                                               + q + 2 * kv)


def test_peaks_by_device_kind():
    p = peaks.peaks_for("TPU v5 lite")
    assert p.flops_bf16 == 197e12 and p.hbm_bytes_s == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("TPU v9 imaginary")
