"""The trace reduction, on synthetic intervals and on a small trace
recorded on a TPU v5e (a tiny DiT served for a fraction of a second)."""
from pathlib import Path

import numpy as np
import pytest

import trace_reduce

FIXTURE = Path(__file__).resolve().parent / "data" / "tiny.xplane.pb"


def test_union_merges_overlaps_and_touching_intervals():
    s, e = trace_reduce.union(np.array([5.0, 0.0, 2.0, 10.0, 10.5]),
                              np.array([6.0, 3.0, 4.0, 11.0, 10.7]))
    assert s.tolist() == [0.0, 5.0, 10.0]
    assert e.tolist() == [4.0, 6.0, 11.0]


def test_union_of_nothing():
    s, e = trace_reduce.union(np.zeros(0), np.zeros(0))
    assert len(s) == len(e) == 0


def test_gaps_go_to_the_innermost_span():
    spans = [("bench.window", 0.0, 100.0), ("bench.tick", 10.0, 50.0),
             ("bench.hook", 40.0, 50.0), ("bench.wait_arrival", 60.0, 90.0)]
    idle = trace_reduce.charge_gaps(np.array([12.0, 42.0, 61.0, 95.0]),
                                    np.array([14.0, 48.0, 89.0, 99.0]),
                                    spans, 0.0, 100.0)
    assert idle == pytest.approx({"bench.tick": 2e-9, "bench.hook": 6e-9,
                                  "bench.wait_arrival": 28e-9,
                                  "host.other": 4e-9})


def test_module_names_lose_their_suffix():
    assert trace_reduce.module_name("jit_tick(12)") == "jit_tick"
    assert trace_reduce.module_name("jit_tick.3") == "jit_tick"
    assert trace_reduce.module_name("jit_want_all_fn") == "jit_want_all_fn"


def test_recorded_trace():
    s = trace_reduce.reduce_file(str(FIXTURE))
    assert s.devices == 1
    assert 0 < s.busy_s < s.window_s
    assert "jit_tick" in s.module_s
    assert sum(s.module_s.values()) <= s.window_s
    # busy plus idle covers the window
    assert s.busy_s + sum(s.idle_s.values()) == pytest.approx(s.window_s,
                                                              rel=1e-6)
    b = s.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    assert all(v > 0 for _, v in b["device_ops"] + b["idle_gaps"])
