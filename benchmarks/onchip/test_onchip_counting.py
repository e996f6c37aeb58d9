"""The counts and the peak table the metrics are built on, and the fleet
generator's promise that every seed runs the same work."""
import json
from pathlib import Path

import numpy as np
import pytest

import counting
import fleetgen

HERE = Path(__file__).resolve().parent
MLP = {"input_dim": 784, "hidden": 128, "num_classes": 10}


def test_sample_epochs_count_only_selected_real_samples():
    sizes = np.array([5, 40, 0, 17])  # client 2 is a padding row
    selected = np.array([True, False, True, True])
    assert counting.sample_epochs(selected, sizes, 5) == (5 + 0 + 17) * 5
    assert counting.sample_epochs(np.zeros(4, bool), sizes, 5) == 0


def test_mlp_operation_count():
    per = counting.flops_per_sample_epoch(MLP)
    assert per == 2 * (784 * 128 + 128 * 10) + 2 * 784 * 128 + 4 * 128 * 10
    assert counting.param_count(MLP) == 101_770
    flops, nbytes = counting.local_sgd_work(2, 300, MLP)
    assert flops == per * 300
    assert nbytes == 300 * 4 * 785 + 2 * 2 * 4 * 101_770


def test_roofline_names_its_bound():
    peaks = counting.chip_peaks("TPU v5 lite")
    t, bound = counting.roofline_seconds(197e12, 1.0, peaks)
    assert (t, bound) == (pytest.approx(1.0), "compute")
    t, bound = counting.roofline_seconds(1.0, 819e9, peaks)
    assert (t, bound) == (pytest.approx(1.0), "memory")


def test_unknown_chip_is_an_error():
    with pytest.raises(ValueError, match="no published peaks"):
        counting.chip_peaks("TPU v9000")


def test_quantile_sizes_are_fixed_and_hold_their_mean():
    spec = {"kind": "lognormal", "mean": 100, "sigma": 1.0, "min": 5,
            "max": 1000}
    a = fleetgen.quantile_sizes(spec, 2048)
    assert np.array_equal(a, fleetgen.quantile_sizes(spec, 2048))
    assert a.min() >= 5 and a.max() <= 1000
    assert abs(a.mean() - 100) < 0.5


@pytest.mark.parametrize("traffic", ["paper12", "qskew2k"])
def test_every_seed_runs_the_same_work(traffic):
    spec = json.loads((HERE / "traffic" / f"{traffic}.json").read_text())
    if "clients" in spec:
        spec = {**spec, "clients": 64}
    a, b = fleetgen.layout(spec), fleetgen.layout(spec)
    for x, y in zip(a, b):
        if isinstance(x, dict):
            assert all(np.array_equal(x[k], y[k]) for k in x)
        else:
            assert np.array_equal(np.asarray(x, dtype=object),
                                  np.asarray(y, dtype=object))


def test_seed_draws_samples_not_sizes():
    spec = {"profiles": [[[2, 3, 4], 0, 30], [[0], 1, 20]],
            "poisoners": [1], "flip_frac": 1.0, "eval_samples": 8}
    f1, f2 = fleetgen.make_fleet(spec, 1), fleetgen.make_fleet(spec, 2**33)
    assert np.array_equal(f1.sizes, f2.sizes)
    assert not np.array_equal(f1.x, f2.x)
    assert set(f1.y[:30]) <= {2, 3, 4}
    # every label of the poisoner, whose only class is 0, is flipped
    assert 0 not in set(f1.y[30:])
    x, y, mask = f1.dense()
    assert x.shape == (2, 30, 784) and mask.sum() == 50
    assert np.array_equal(x[1, :20], f1.x[30:])
