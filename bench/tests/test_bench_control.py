"""The control (the reference in bfloat16 at the default precision, put in
the program's place) comes out not correct, at a size a test can hold."""

from bench.calibrate import calibrate
from bench.lib import spec as specs
from bench.tests.benchtest import BENCHMARK, SEED


def _fails(cell, readings):
    limits = specs.load_cell(cell, BENCHMARK).limits
    control = [r["numbers"] for r in readings if r["kind"] == "control"]
    assert control
    return all(any(n[k] > lim for k, lim in limits.items()) for n in control)


def test_training_control_fails():
    readings = calibrate("tiny.train", [], [SEED], [], benchmark=BENCHMARK, allow_cpu=True)
    assert _fails("tiny.train", readings)


def test_sampling_control_fails():
    readings = calibrate("tiny.sample", [], [SEED], [], seconds=0.2, benchmark=BENCHMARK,
                         allow_cpu=True)
    assert _fails("tiny.sample", readings)
