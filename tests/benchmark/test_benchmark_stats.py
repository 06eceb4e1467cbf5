"""The percentile rule, the latencies a client sees, and the traffic
generator: all arithmetic, no device."""

import numpy as np
import pytest

from benchmark import traffic
from benchmark.evidence import reduce_samples
from benchmark.stats import (
    RequestSample, highest_reportable_percentile, ms, percentile,
)


@pytest.mark.parametrize("q,want", [(0.5, 50), (0.9, 90), (0.99, 99), (1.0, 100), (0.001, 1)])
def test_percentile_is_nearest_rank(q, want):
    assert percentile(list(range(100, 0, -1)), q) == want


def test_percentile_is_a_measured_value_and_refuses_nothing():
    assert percentile([3.0, 1.0], 0.5) == 1.0
    assert percentile([7.0], 0.9) == 7.0
    with pytest.raises(ValueError):
        percentile([], 0.5)
    with pytest.raises(ValueError):
        percentile([1.0], 0.0)


@pytest.mark.parametrize("n,want", [(100, 0.9), (1000, 0.99), (40, 0.75), (0, 0.0)])
def test_highest_percentile_with_ten_samples_beyond(n, want):
    assert highest_reportable_percentile(n) == pytest.approx(want)


def test_latency_runs_from_the_due_time_not_the_send():
    s = RequestSample(0, 100, 5, t_due=10.0, t_sent=10.25, t_first=11.0,
                      t_last=11.8, t_done=11.8, n_out=5, ok=True)
    assert s.ttft_s == pytest.approx(1.0)            # due → first token
    assert s.ttft_from_send_s == pytest.approx(0.75)
    assert s.lateness_s == pytest.approx(0.25)       # how late the generator ran
    assert s.tpot_s == pytest.approx(0.8 / 4)        # (t_last - t_first) / (n - 1)
    assert ms([s.ttft_s, None]) == [pytest.approx(1000.0)]


def test_tpot_is_undefined_for_one_token_and_unanswered_requests():
    assert RequestSample(0, 1, 1, 0.0, t_first=1.0, t_last=1.0, n_out=1).tpot_s is None
    assert RequestSample(0, 1, 4, 0.0).ttft_s is None


@pytest.mark.parametrize("how,want", [("p50", 2.0), ("p90", 4.0), ("mean", 2.5), ("max", 4.0)])
def test_reductions(how, want):
    assert reduce_samples([4.0, 1.0, 2.0, 3.0], how) == want
    assert reduce_samples([], how) is None


def test_lengths_follow_the_mix_and_the_seed():
    spec = {"dist": "lognormal", "median": 3000, "sigma": 0.6, "min": 1024, "max": 8192}
    a = traffic.draw_lengths(spec, 4000, np.random.default_rng(1))
    b = traffic.draw_lengths(spec, 4000, np.random.default_rng(1))
    assert (a == b).all() and a.min() >= 1024 and a.max() <= 8192
    assert 2700 < np.median(a) < 3300
    assert (a > 4096).mean() > 0.2  # the window of 4096 bites on the upper tail
    # stratified: every block of 16 draws is the same multiset on any seed
    c = traffic.draw_lengths(spec, 4000, np.random.default_rng(2))
    assert (a != c).any()
    assert sorted(a[:16]) == sorted(c[:16]) == sorted(a[16:32])
    assert a[:160].sum() == c[:160].sum()
    with pytest.raises(ValueError):
        traffic.draw_lengths({"dist": "zipf"}, 1, np.random.default_rng(0))


def test_arrivals_are_plain_poisson():
    """Not stratified: an open loop is there for its bursts and lulls."""
    spec = {"process": "poisson", "rate_rps": 1.25}
    counts = [len(traffic.arrival_offsets(spec, 48.0, seed=s)) for s in range(200)]
    # a Poisson count: variance equal to the mean (60)
    assert np.mean(counts) == pytest.approx(60, rel=0.05)
    assert np.var(counts) == pytest.approx(60, rel=0.35)
    a = np.diff([0.0] + traffic.arrival_offsets(spec, 4000.0, seed=1))
    # the gaps are exponential: mean 1/rate, a quarter of them under 0.29/rate
    assert a.mean() == pytest.approx(0.8, rel=0.05)
    assert (a < -np.log(0.75) * 0.8).mean() == pytest.approx(0.25, abs=0.04)


@pytest.mark.parametrize("rate", [0.5, 20.0])
def test_arrivals_are_a_value_of_the_seed(rate):
    spec = {"process": "poisson", "rate_rps": rate}
    a = traffic.arrival_offsets(spec, 400.0, seed=5)
    assert a == traffic.arrival_offsets(spec, 400.0, seed=5)
    assert a != traffic.arrival_offsets(spec, 400.0, seed=6)
    assert a == sorted(a) and 0 <= a[0] and a[-1] < 400.0
    assert len(a) == pytest.approx(rate * 400, rel=0.2)
    assert traffic.arrival_offsets(spec, 0.0, seed=5) == []


def test_arrivals_match_the_programs_generator():
    """Copied from loadgen/arrivals.py: same seed, same schedule."""
    from kubeflow_tpu.loadgen.arrivals import PoissonArrivals

    assert traffic.arrival_offsets({"process": "poisson", "rate_rps": 3.0}, 30.0, 4) == list(
        PoissonArrivals(3.0, 30.0, seed=4).schedule())
    with pytest.raises(ValueError, match="unknown arrival process"):
        traffic.arrival_offsets({"process": "onoff"}, 30.0, 4)


def test_requests_are_a_value_of_the_seed_and_share_no_prefix():
    mix = {"prompt_tokens": {"dist": "lognormal", "median": 40, "sigma": 0.3, "min": 24, "max": 64},
           "output_tokens": {"dist": "lognormal", "median": 6, "sigma": 0.3, "min": 4, "max": 8}}
    plain = traffic.make_requests(mix, 1000, seed=2, n=6)
    assert plain == traffic.make_requests(mix, 1000, seed=2, n=6)
    assert plain != traffic.make_requests(mix, 1000, seed=3, n=6)
    assert len({r.prompt[:16] for r in plain}) == 6
    assert all(2 <= t < 1000 for r in plain for t in r.prompt)
    assert all(24 <= len(r.prompt) <= 64 and 4 <= r.max_new_tokens <= 8 for r in plain)
    due = [0.5 * i for i in range(6)]
    assert [r.due_s for r in traffic.make_requests(mix, 1000, seed=2, n=6, due=due)] == due


def test_training_batches_depend_on_seed_and_step_alone():
    f = traffic.token_batches(512, 16, 4, seed=9)
    first = next(f(0))
    assert first["inputs"].shape == (4, 16) and first["inputs"].dtype == np.int32
    assert (first["inputs"][:, 1:] == first["targets"][:, :-1]).all()
    it = f(0)
    next(it)
    assert (next(it)["inputs"] == next(f(1))["inputs"]).all()   # resumable
    assert (next(traffic.token_batches(512, 16, 4, seed=8)(0))["inputs"] != first["inputs"]).any()
