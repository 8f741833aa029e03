"""The traffic generator: the inverse-CDF sampler draws the program's zipf
distribution, rows are distinct, and a seed gives the same traffic."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench import loadgen
from repro.core.workloads import zipf_probs

BENCH = Path(__file__).resolve().parents[1]


@pytest.mark.parametrize("n,theta", [(64, 0.9), (1000, 0.99), (50, 0.5)])
def test_inverse_cdf_matches_zipf_probs(n, theta):
    rng = np.random.default_rng(7)
    draws = 400_000
    got = np.bincount(loadgen.draw_records(
        rng, loadgen.zipf_cdf(n, theta), n, draws), minlength=n) / draws
    want = zipf_probs(n, theta)
    # every cell within 5 binomial standard deviations, and Pearson's
    # chi-square within 6 of its standard deviations of its mean
    sd = np.sqrt(want * (1 - want) / draws)
    assert np.all(np.abs(got - want) <= 5 * sd + 1e-12)
    chi2 = draws * np.sum((got - want) ** 2 / want)
    assert chi2 < (n - 1) + 6 * np.sqrt(2 * (n - 1))


def test_uniform_draws_cover_the_table():
    rng = np.random.default_rng(3)
    assert loadgen.zipf_cdf(100, 0.0) is None
    got = loadgen.draw_records(rng, None, 100, 100_000)
    assert got.min() == 0 and got.max() == 99
    assert np.bincount(got).min() > 800


def test_rows_are_distinct_even_on_a_tiny_hot_table():
    rng = np.random.default_rng(11)
    rows = loadgen.distinct_rows(rng, loadgen.zipf_cdf(12, 0.99), 12,
                                 500, 10)
    assert rows.shape == (500, 10)
    assert all(len(set(r)) == 10 for r in rows.tolist())


@pytest.mark.parametrize("traffic", sorted(
    p.stem for p in (BENCH / "traffic").glob("*.json")))
def test_every_mix_loads_and_draws(traffic, monkeypatch):
    mix = loadgen.load_mix(BENCH / "traffic" / f"{traffic}.json")
    assert mix.name == traffic
    monkeypatch.setattr(loadgen, "POOL_BATCHES", 2)
    monkeypatch.setattr(loadgen, "SCAN_POOL_BATCHES", 2)
    seed = 2**31 + 77                       # larger than 32 signed bits
    a = loadgen.update_pool(seed, mix, 4096, 64)
    b = loadgen.update_pool(seed, mix, 4096, 64)
    c = loadgen.update_pool(seed + 1, mix, 4096, 64)
    assert all(np.array_equal(x.read_set, y.read_set) and
               np.array_equal(x.write_set, y.write_set)
               for x, y in zip(a, b))
    assert not np.array_equal(a[0].read_set, c[0].read_set)
    for hb in a:
        assert hb.read_set.dtype == np.int32
        w = hb.write_set
        # every write is an RMW of the record read in the same column
        assert np.all((w < 0) | (w == hb.read_set))
        assert hb.n_writes == {"10rmw": 10, "2rmw8r": 2}[mix.mix] * 64
    if mix.has_scans:
        scans = loadgen.scan_pool(seed, mix, 4096, 64)
        assert len(scans) == 2
        assert np.all(scans[0].write_set == -1)


def test_traffic_files_hold_only_known_keys(tmp_path):
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"name": "bad", "mix": "10rmw", "ops": 10,
                               "theta": 0, "outstanding": 1,
                               "rate": 5}))
    with pytest.raises(ValueError, match="rate"):
        loadgen.load_mix(bad)


def test_sample_mask_is_drawn_from_the_seed():
    a = loadgen.sample_mask(5, 0, 64)
    assert np.array_equal(a, loadgen.sample_mask(5, 0, 64))
    assert not np.array_equal(a, loadgen.sample_mask(5, 1, 64))
    assert 0.01 < a.mean() < 0.02
