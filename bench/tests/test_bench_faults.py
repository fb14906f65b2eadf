"""The rest of a run, without the look for a chip, at a small size on
the CPU: a sound run is correct, and with the timed path broken
underneath (bench/faults.py) ``correct`` comes out false, once for each
fault the cell can have. The control (the reference in float8 in the
program's place) reads well above the program, and ``check_control``
judges it not correct against the cell's limits."""
import jax
import pytest

from bench import check_control as CC
from bench import faults
from bench import run as R
from bench.tests import tiny

# Limits at this small size, between the program's readings (loss 2e-4,
# gradient 2.5e-3, change 8.5e-4 over a few seeds) and the control's
# (gradient >= 1.8e-2, change >= 7e-3); the cells' own limits are set
# from chip runs at their sizes.
TRAIN_LIMITS = {"loss_gap": 1e-3, "grad_gap": 8e-3, "change_gap": 4e-3}

CASES = [
    ("stablelm-1.6b.train-2k", 1, None),
    ("stablelm-1.6b.train-2k", 1, "state_unchanged"),
    ("stablelm-1.6b.train-2k", 1, "half_batch"),
    ("gpt-paper-20b.train-4d", 4, None),
    ("gpt-paper-20b.train-4d", 4, "no_exchange"),
]


def _cell(name, chips):
    cfg = tiny.config("layernorm-plain-gelu-mha-partial-rotary" if chips == 4
                      else "layernorm-gated-mha-partial-rotary")
    return tiny.cell(name, cfg, TRAIN_LIMITS, chips)


@pytest.mark.parametrize("name,chips,fault", CASES)
def test_fault_makes_the_run_incorrect(name, chips, fault):
    cell = _cell(name, chips)
    devices = jax.devices()[:chips]
    if fault is None:
        out, checks = R.execute(cell, 2**31 + 3, 0.3, False, devices)
        assert out["correct"], checks
        assert out["failed"] == 0 and out["attempted"] > 0
        assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}
        return
    with faults.FAULTS[fault]():
        out, checks = R.execute(cell, 2**31 + 3, 0.3, False, devices)
    assert not out["correct"], checks


def test_control_reads_above_the_program():
    cell = _cell("stablelm-1.6b.train-2k", 1)
    rows = CC.train_readings(cell, jax.devices()[:1], [2**31 + 5], 1)
    prog, ctrl = rows[:2]
    assert prog["kind"] == "program" and ctrl["kind"] == "control"
    assert ctrl["grad_gap"] > 3 * prog["grad_gap"]
    assert ctrl["grad_gap"] > TRAIN_LIMITS["grad_gap"]
    # judged against the cell's limits, as a run is
    assert prog["correct"] and not ctrl["correct"]
    summary = CC.summary(rows)
    assert summary["program"]["correct"] == "1/1"
    assert summary["control"]["correct"] == "0/1"
