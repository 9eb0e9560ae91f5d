"""The traffic generator: deterministic by seed; arrivals and sizes fixed
by the mix's own traffic seed, the run's seed drawing labels and noise."""
import collections
import statistics

import pytest

import traffic

MIX = {"arrivals": "poisson", "rate_per_s": 5.0,
       "steps": {"50": 0.75, "25": 0.25}, "cfg_scale": 4.0,
       "traffic_seed": 0}


def test_same_seed_same_requests():
    assert traffic.generate(MIX, 7, 200, 1000) == \
        traffic.generate(MIX, 7, 200, 1000)


def test_prefix_does_not_depend_on_count():
    assert traffic.generate(MIX, 7, 300, 1000)[:40] == \
        traffic.generate(MIX, 7, 40, 1000)


def test_other_seed_same_arrivals_and_sizes():
    a = traffic.generate(MIX, 7, 128, 1000)
    b = traffic.generate(MIX, 8, 128, 1000)
    assert [r.due_s for r in a] == [r.due_s for r in b]
    assert [r.num_steps for r in a] == [r.num_steps for r in b]
    assert [r.label for r in a] != [r.label for r in b]
    assert [r.noise_seed for r in a] != [r.noise_seed for r in b]


def test_other_traffic_seed_other_arrivals():
    a = traffic.generate(MIX, 7, 64, 1000)
    b = traffic.generate(dict(MIX, traffic_seed=1), 7, 64, 1000)
    assert [r.due_s for r in a] != [r.due_s for r in b]


def test_arrivals_are_poisson():
    reqs = traffic.generate(MIX, 1, 4000, 1000)
    gaps = [b.due_s - a.due_s for a, b in zip(reqs, reqs[1:])]
    mean = statistics.fmean(gaps)
    assert mean == pytest.approx(1 / 5.0, rel=0.05)
    # an exponential's standard deviation equals its mean
    assert statistics.stdev(gaps) == pytest.approx(mean, rel=0.08)
    assert max(gaps) > 6 * mean
    share = collections.Counter(r.num_steps for r in reqs)[50] / len(reqs)
    assert share == pytest.approx(0.75, abs=0.03)


def test_rate_scales_the_same_arrivals():
    a = traffic.generate(MIX, 7, 50, 1000)
    b = traffic.generate(dict(MIX, rate_per_s=10.0), 7, 50, 1000)
    assert [2 * r.due_s for r in b] == pytest.approx([r.due_s for r in a])


def test_large_seed_and_ranges():
    reqs = traffic.generate(MIX, 2**31 + 12345, 64, 1000)
    assert all(0 <= r.label < 1000 for r in reqs)
    assert all(0 <= r.noise_seed < 2**31 for r in reqs)
    assert all(r.cfg_scale == 4.0 for r in reqs)
    assert [r.rid for r in reqs] == list(range(64))
    assert all(b.due_s > a.due_s for a, b in zip(reqs, reqs[1:]))


def test_backlog_is_due_at_once():
    mix = dict(MIX, arrivals="backlog", steps={"50": 1.0})
    reqs = traffic.generate(mix, 3, 40, 1000)
    assert {r.due_s for r in reqs} == {0.0}
    assert {r.num_steps for r in reqs} == {50}


def test_bad_mix_is_refused():
    with pytest.raises(ValueError):
        traffic.generate(dict(MIX, arrivals="bursty"), 1, 4, 10)
    with pytest.raises(ValueError):
        traffic.generate(MIX, -1, 4, 10)
    with pytest.raises(ValueError):
        traffic.generate(dict(MIX, block=8), 1, 4, 10)
