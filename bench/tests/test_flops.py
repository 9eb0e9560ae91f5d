"""Analytic backbone FLOPs against the published hand counts."""
import json

import pytest

import tiny

from harness import Bench


def _model(name):
    return json.loads((tiny.BENCH / "configs" / f"{name}.json")
                      .read_text())["model"]


def test_dit_xl_row_is_118_6_g_macs():
    flops = Bench(tiny.CHECKOUT).module("flops", "dit")
    # DiT paper, Table 4: DiT-XL/2 at 256 px, 118.6 "Gflops" (MACs)
    assert flops.macs_per_row(_model("dit-xl")) / 1e9 == \
        pytest.approx(118.6, abs=0.05)
    assert flops.flops_per_row(_model("dit-xl")) == \
        2 * flops.macs_per_row(_model("dit-xl"))


def test_latte_row_by_hand():
    flops = Bench(tiny.CHECKOUT).module("flops", "video_dit")
    d, Fd, N, P, F, L = 1152, 4608, 4096, 256, 16, 28
    per_block = 8 * N * d * d + 2 * N * P * d + 2 * N * F * d \
        + 2 * N * d * Fd + 9 * d * d
    want = L * per_block + 2 * N * d * 16 + 4 * d * d
    assert flops.macs_per_row(_model("dit-video")) == want
    assert flops.flops_per_row(_model("dit-video")) / 1e12 == \
        pytest.approx(5.0, rel=0.01)
