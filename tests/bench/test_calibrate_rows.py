"""``bench.calibrate_rows``: the faults it plants for a cell whose batch is
too small for the half-batch fault each read past a limit, and a recovery
that leaves the lost stage as it was reads past ``recovery_gap`` (CPU,
tiny)."""
import pytest

from bench import calibrate_rows, check
from tiny import TINY_LIMITS, tiny_config

TRAINING = ("grad_gap", "grad_diff", "change_gap", "omega_gap")


@pytest.fixture(scope="module")
def config():
    return tiny_config("mamba2-1.3b.L8")


@pytest.fixture(scope="module")
def reference(config):
    return check.reference_capture(config, 5, 4)


@pytest.mark.parametrize("fault", calibrate_rows.FAULTS)
def test_each_fault_reads_past_a_training_limit(config, reference, fault):
    got = check.readings(calibrate_rows.fault_capture(config, 5, 4, fault),
                         reference)
    assert any(got[k] > TINY_LIMITS[k] for k in TRAINING), got


def test_each_fault_leaves_out_what_it_names():
    import numpy as np
    batch = {"tokens": np.zeros((2, 16)), "targets": np.ones((2, 16))}
    rows = calibrate_rows._fault_batch("row_dropped", batch)
    half = calibrate_rows._fault_batch("half_tokens", batch)
    assert {k: v.shape for k, v in rows.items()} == {
        "tokens": (1, 16), "targets": (1, 16)}
    assert {k: v.shape for k, v in half.items()} == {
        "tokens": (2, 8), "targets": (2, 8)}


def test_a_stage_left_as_it_was_reads_past_recovery_gap(config):
    gaps = calibrate_rows.stage_left_gaps(config, 5)
    assert sorted(gaps) == ["0", "1", "2", "3"]
    assert min(gaps.values()) > TINY_LIMITS["recovery_gap"], gaps
