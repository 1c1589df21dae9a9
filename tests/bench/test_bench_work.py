"""Operation and byte counts of the benchmark, against hand counts, and
the table of peaks."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import peaks, reference, work  # noqa: E402


def test_first_layer_counts_the_client_slice_only():
    # 4 rows of a 3-wide slice into 2 hidden units: 4*3*2 multiply-adds
    flops, moved = work.first_layer(4, 3, 2)
    assert flops == 2 * 4 * 3 * 2
    assert moved == 4 * (4 * 3 + 3 * 2 + 4 * 2)


@pytest.mark.parametrize("kind,n_features,n_clients",
                         [("image_rows", 16, 3), ("round_robin", 7, 2)])
def test_first_layer_work_is_the_same_for_masked_and_sliced(
        kind, n_features, n_clients):
    # the masked lane multiplies a zero-padded [B, F] batch by all F
    # rows; the count covers only the slice rows, so it equals the
    # sum over clients and never the padded n * F * H
    widths = [len(p) for p in reference.partition(kind, n_features,
                                                  n_clients)]
    assert sum(widths) == n_features
    per_client = sum(work.first_layer(5, w, 3)[0] for w in widths)
    assert per_client == 2 * 5 * n_features * 3
    assert per_client < n_clients * 2 * 5 * n_features * 3


def test_step_flops_per_sample_by_hand():
    # widths (3, 2), hidden 4, 2 hidden layers, 5 classes:
    # forward  first 2*4*(3+2) = 40, rest 2 * (2*4*4 + 2*4*5) = 144
    # backward first 40 (weights only), rest 2 * 144
    assert work.step_flops_per_sample((3, 2), 4, 2, 5) == \
        40 + 144 + 40 + 288


def test_least_time_names_its_bound():
    peak = peaks.peak("TPU v5 lite")
    t, bound = work.least_seconds(*work.first_layer(64, 168, 10), peak)
    assert bound == "memory"
    assert t == pytest.approx(4 * (64 * 168 + 168 * 10 + 64 * 10) / 819e9)
    t, bound = work.least_seconds(1e12, 1.0, peak)
    assert bound == "compute" and t == pytest.approx(1e12 / 197e12)


def test_peaks_are_keyed_by_device_kind_with_a_source():
    p = peaks.peak("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16e9
    assert "TPU v5e" in p["source"]


def test_unknown_device_kind_is_refused():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peak("cpu")
