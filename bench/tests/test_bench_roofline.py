"""The algorithmic byte count of a slot step, owned by each policy's
reference, and the peak table."""
import pytest

from bench import harness

SIZES = {"L": 1000, "K": 16, "Qcap": 4096, "J": 4, "A_max": 64,
         "mu": 0.01}


def test_vqs_bf_slot_bytes_from_shapes():
    # L x K sizes and durations, L residuals, 3 ring planes of 2J x Qcap,
    # all int32, read and written; A_max arrivals (size, duration) read
    words = 2 * 1000 * 16 + 1000 + 3 * 8 * 4096
    assert harness.reference_module("vqs-bf").slot_bytes(SIZES) \
        == 2 * 4 * words + 2 * 4 * 64 == 1_050_944


def test_bfjs_slot_bytes_from_shapes():
    # L x K sizes and departure slots, Qcap queued sizes, read and
    # written; A_max arrivals read
    words = 2 * 1000 * 16 + 4096
    assert harness.reference_module("bfjs").slot_bytes(SIZES) \
        == 2 * 4 * words + 2 * 4 * 64 == 289_280


def test_slot_bytes_reads_the_config_sizes():
    ref = harness.reference_module("vqs-bf")
    assert ref.slot_bytes(dict(SIZES, K=8)) < ref.slot_bytes(SIZES)
    with pytest.raises(KeyError, match="no reference for policy 'fifo-ff'"):
        harness.reference_module("fifo-ff")


def test_peak_table_lookup():
    v5e = harness.device_peaks("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["hbm_bytes"] == 16e9


def test_an_unknown_device_is_an_error():
    with pytest.raises(KeyError, match="TPU v9 imaginary"):
        harness.device_peaks("TPU v9 imaginary")
