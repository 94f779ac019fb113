"""A training run with the timed path broken underneath comes out not
correct: a step that leaves the state unchanged, and a loss over half of
the batch.  The sound run comes out correct."""

import pytest

from bench.tests.benchtest import run_tiny


@pytest.mark.parametrize("fault", [None, "frozen", "half_batch"])
def test_training_faults_fail_the_check(fault):
    result, err = run_tiny("tiny.train", fault=fault)
    assert result["correct"] is (fault is None), err
    assert list(result["checks"]) == ["loss_gap", "grad_gap", "update_gap"]
    assert err.rstrip().splitlines()[-1].startswith("check update_gap ")
    assert set(result["metrics"]) == {"train_samples_per_s", "peak_hbm_gib", "setup_s"}
    # every step of the window returned a finite loss
    assert result["attempted"] >= 1 and result["failed"] == 0
