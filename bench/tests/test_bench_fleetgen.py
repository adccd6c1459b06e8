"""The generator: a seed fixes the deployment and the arrivals."""
import json
import os

import numpy as np
from conftest import BENCH

import fleetgen


def _load(kind, name):
    with open(os.path.join(BENCH, kind, f"{name}.json")) as f:
        return json.load(f)


def _fleet(seed, periods=16):
    config = {**_load("configs", "campus1k"), "n_devices": 96}
    return fleetgen.make_fleet(config, _load("traffic", "frames"), periods,
                               seed)


def test_same_seed_same_fleet():
    a, b = _fleet(2**31 + 17), _fleet(2**31 + 17)
    for f in ("p_ed", "p_es", "acc", "drift", "outage", "counts", "stream"):
        np.testing.assert_array_equal(getattr(a, f), getattr(b, f))


def test_seeds_differ_but_shapes_do_not():
    a, b = _fleet(1), _fleet(2)
    assert not np.array_equal(a.stream, b.stream)
    for f in ("p_ed", "p_es", "acc", "drift", "outage", "counts", "stream"):
        assert getattr(a, f).shape == getattr(b, f).shape
        assert getattr(a, f).dtype == getattr(b, f).dtype


def test_the_papers_frames():
    """Every device has the paper's latencies and n = batch_max jobs in
    every frame; none drifts or loses its link."""
    f = _fleet(3, periods=64)
    config = _load("configs", "campus1k")
    prof = config["paper_profile"]
    np.testing.assert_array_equal(f.p_ed, np.broadcast_to(
        np.asarray(prof["p_ed"]), f.p_ed.shape))
    np.testing.assert_array_equal(f.p_es[0], np.add(prof["comm"],
                                                    prof["p_es_proc"]))
    assert (f.drift == 1).all() and not f.outage.any()
    assert (f.counts == config["batch_max"]).all()
    assert set(np.unique(f.stream)) == {0, 1, 2}
    assert f.stream.shape[1] == config["batch_max"] * 64


def test_optional_draws():
    """Poisson arrivals, jitter, stragglers and outages, where a
    configuration asks for them."""
    config = {**_load("configs", "campus1k"), "n_devices": 512,
              "straggler_frac": 0.25, "drift_mag": 3.0, "outage_frac": 0.1,
              "outage_prob": 0.2, "schedule_periods": 64}
    config["paper_profile"] = {**config["paper_profile"],
                               "jitter_ed": [0.8, 1.3]}
    traffic = {**_load("traffic", "frames"), "arrivals": "poisson",
               "rate": 10.0}
    f = fleetgen.make_fleet(config, traffic, 64, 3)
    assert abs(f.counts.mean() - 10.0) < 0.5
    assert 0.15 < (f.drift.max(axis=1) > 1).mean() < 0.35
    assert 0.05 < f.outage.any(axis=1).mean() < 0.15
    assert f.p_ed.std(axis=0).max() > 0
