"""The harness's plumbing on the CPU: it refuses to run without an
accelerator, finds every file of every cell by name, and draws the same
work from every seed."""
import jax
import numpy as np
import pytest

from bench import common as C
from bench import compare
from bench import run as R
from bench import traffic as TR

BJ = C.load_json(C.ROOT / "BENCHMARK.json")
CELLS = [w["name"] for w in BJ["workloads"]]


def test_no_accelerator_no_result(capsys):
    with pytest.raises(SystemExit) as e:
        R.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                "--trace", "0"])
    assert e.value.code not in (None, 0)
    assert capsys.readouterr().out == ""


@pytest.mark.parametrize("name", CELLS)
def test_cell_files_are_found_by_name(name):
    cell = C.load_cell(name)
    assert cell.spec["driver"] in R.DRIVERS
    assert cell.traffic["kind"] == "train"
    assert {"setup_s"} < {m["name"] for m in cell.end_to_end}
    assert cell.per_layer
    for m in cell.per_layer:
        assert callable(C.load_reader(m["name"]))
    # every compared number has a limit
    assert cell.spec["limits"] and all(
        v is not None for v in cell.spec["limits"].values())


def test_readers_find_nothing_in_an_empty_context():
    for m in BJ["per_layer"]:
        assert C.load_reader(m["name"])({}) is None


def test_large_seeds_give_distinct_keys():
    a, b = C.base_key(3), C.base_key(3 + 2**32)
    assert not np.array_equal(jax.random.key_data(a),
                              jax.random.key_data(b))


def test_training_batches_differ_and_are_shifted():
    t = {"global_batch": 2, "seq_len": 16, "markov_states": 8}
    b = TR.train_batches(t, 512, 2**40 + 7, 3)
    assert all(x.shape == (2, 16) for tok, lab in b for x in (tok, lab))
    assert not np.array_equal(b[0][0], b[1][0])
    np.testing.assert_array_equal(b[0][0][:, 1:], b[0][1][:, :-1])


@pytest.mark.parametrize("value,limit,ok", [
    (1e-4, 1e-3, True),
    (1e-3, 1e-3, True),
    (2e-3, 1e-3, False),
    (float("nan"), 1e-3, False),
    (1e-4, None, False),
])
def test_judge(value, limit, ok):
    correct, checks = compare.judge({"loss_gap": value},
                                    {"loss_gap": limit})
    assert correct is ok
    assert checks["loss_gap"]["limit"] == limit


def test_limits_lie_between_the_readings():
    from bench import check_control as CC
    rows = [{"kind": "program", "loss_gap": 1e-4, "change_gap": 2e-4},
            {"kind": "program", "loss_gap": 2e-4, "change_gap": 1e-4},
            # the control counts on the change (over 3x), not on the loss
            {"kind": "control", "loss_gap": 5e-4, "change_gap": 8e-4},
            {"kind": "control", "loss_gap": 9e-4, "change_gap": 9e-4},
            # a fault counts from 10x: on the loss here
            {"kind": "half_batch", "loss_gap": 4e-3, "change_gap": 1.5e-3}]
    p = CC.propose(rows)
    assert p["loss_gap"]["lower"] == 2e-4 and p["loss_gap"]["upper"] == 4e-3
    assert p["change_gap"]["upper"] == 8e-4
    for k, v in p.items():
        assert v["lower"] < v["limit"] < v["upper"], (k, v)
        # more of the room above the lower
        assert v["limit"] / v["lower"] > v["upper"] / v["limit"], (k, v)
    # nothing over its bar: no upper, no limit
    none = CC.propose(rows[:2] + [dict(rows[2], loss_gap=3e-4)])
    assert none["loss_gap"]["upper"] is None
    assert none["loss_gap"]["limit"] is None
