"""A sampling run whose answers are altered where they are produced comes
out not correct; the sound run comes out correct."""

import pytest

from bench.tests.benchtest import run_tiny


@pytest.mark.parametrize("fault", [None, "altered"])
def test_sampling_faults_fail_the_check(fault):
    result, err = run_tiny("tiny.sample", fault=fault)
    assert result["correct"] is (fault is None), err
    assert set(result["metrics"]) == {"draws_per_s", "request_p95_ms", "peak_hbm_gib",
                                      "setup_s"}
    assert result["attempted"] >= 1 and result["failed"] == 0
