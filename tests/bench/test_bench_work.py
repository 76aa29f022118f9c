"""Operations, bytes and peaks the benchmark's metrics divide by: hand
counts for both configurations, kernel work that ignores the lane pad,
the counts the metrics multiply by pinned to what they were before they
moved into the architecture module, and a peaks table that refuses an
unknown chip."""

from __future__ import annotations

import json
import math

import numpy as np
import pytest

from _tiny import ROOT

from bench import harness, peaks, work  # noqa: E402

YI = json.loads((ROOT / "bench" / "configs" / "yi9b-1l.json").read_text())
PHI = json.loads((ROOT / "bench" / "configs" / "phi3v-1l.json").read_text())
DECODER = harness.load_arch(ROOT, "decoder")
SEQ = 4096
PAIRS = SEQ * (SEQ + 1) // 2


def test_hand_counts_yi():
    layer = 2 * 4096 * 4096 + 2 * 4096 * 512 + 3 * 4096 * 11008
    assert DECODER.layer_matmul_params(YI) == layer == 173_015_040
    assert DECODER.param_count(YI) == layer + 2 * 4096 * 8000 + 3 * 4096
    assert round(DECODER.param_count(YI) / 1e6, 1) == 238.6
    attn = 12 * 128 * 32 * PAIRS / SEQ
    flops = DECODER.train_flops_per_position(YI, SEQ)
    assert flops == pytest.approx(6 * (layer + 4096 * 8000) + attn)
    assert round(flops / 1e9, 2) == 1.34


def test_hand_counts_phi():
    layer = 4 * 3072 * 3072 + 3 * 3072 * 8192
    assert DECODER.layer_matmul_params(PHI) == layer == 113_246_208
    assert round(DECODER.param_count(PHI) / 1e6, 1) == 137.9
    flops = DECODER.train_flops_per_position(PHI, SEQ)
    assert flops == pytest.approx(6 * (layer + 3072 * 4008)
                                  + 12 * 96 * 32 * PAIRS / SEQ)
    assert round(flops / 1e9, 2) == 0.83


@pytest.mark.parametrize("conf", [YI, PHI])
def test_param_count_is_the_weights_made(conf):
    shapes = DECODER.param_shapes(conf)
    sizes = []

    def walk(t):
        for v in t.values():
            walk(v) if isinstance(v, dict) else sizes.append(math.prod(v))

    walk(shapes)
    assert sum(sizes) == DECODER.param_count(conf)


def test_flash_work_uses_the_published_head_dim():
    """Phi's head_dim 96 is padded to 128 lanes inside the kernel; the
    work counted is the 96 the algorithm needs, whatever the pad."""
    flops, nbytes = work.flash_work_per_row(SEQ, 32, 32, 96)
    assert flops == 12 * 96 * 32 * PAIRS
    assert work.flash_work_per_row(SEQ, 32, 32, 128)[0] == flops * 128 / 96
    q = SEQ * 32 * 96 * 4
    lse = SEQ * 32 * 4
    # forward: q, k, v in, o and lse out; backward: q, k, v, o, dO, lse
    # in, dq, dk, dv out (MHA: k and v as wide as q)
    assert nbytes == (3 * q + q + lse) + (5 * q + lse + 3 * q)
    f3, b3 = DECODER.kernel_work(PHI, SEQ, 3)["attention"]
    assert (f3, b3) == (3 * flops, 3 * nbytes)


def test_gqa_kernel_bytes_count_kv_heads():
    _, nbytes = DECODER.kernel_work(YI, SEQ, 1)["attention"]
    q, kv, lse = SEQ * 32 * 128 * 4, SEQ * 4 * 128 * 4, SEQ * 32 * 4
    assert nbytes == (q + 2 * kv + q + lse) + (3 * q + 2 * kv + lse
                                               + q + 2 * kv)


# What ``mfu`` and ``flash_roofline`` multiply by, as the counts gave them
# before they moved into bench/arch/decoder.py: the same to the last digit.
PINNED = {
    "phi3v-1l": (PHI, 828868608.0, 137880576, {
        1: (309313142784.0, 605028352.0),
        7: (2165191999488.0, 4235198464.0),
        12: (3711757713408.0, 7260340224.0)}),
    "yi9b-1l": (YI, 1335386112.0, 238563328, {
        1: (412417523712.0, 454033408.0),
        7: (2886922665984.0, 3178233856.0),
        12: (4949010284544.0, 5448400896.0)}),
}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_counts_are_pinned(name):
    conf, flops, params, kernel = PINNED[name]
    assert DECODER.train_flops_per_position(conf, SEQ) == flops
    assert DECODER.param_count(conf) == params
    for rows, want in kernel.items():
        assert DECODER.kernel_work(conf, SEQ, rows) == {"attention": want}


@pytest.mark.parametrize("seq,window", [(1, None), (7, None), (16, 1),
                                        (16, 5), (16, 16), (16, 40),
                                        (128, 48)])
def test_attention_pairs_against_a_brute_force_mask(seq, window):
    q, k = np.meshgrid(np.arange(seq), np.arange(seq), indexing="ij")
    keep = k <= q
    if window is not None:
        keep &= q - k < window
    assert work.attention_pairs(seq, window) == int(keep.sum())


def test_windowed_flash_work_counts_the_kept_pairs():
    full, nbytes = work.flash_work_per_row(SEQ, 32, 4, 128)
    win, wbytes = work.flash_work_per_row(SEQ, 32, 4, 128, window=1024)
    assert win == full * work.attention_pairs(SEQ, 1024) / PAIRS
    assert wbytes == nbytes       # every tensor is read or written once
    assert work.flash_work(SEQ, 32, 4, 128, 5, window=1024) == (5 * win,
                                                                5 * wbytes)


def test_peaks_by_device_kind():
    p = peaks.peaks_for("TPU v5 lite")
    assert p.flops_bf16 == 197e12 and p.hbm_bytes_s == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("TPU v9 imaginary")
