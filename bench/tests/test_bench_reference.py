"""The plain reference against the program at a small size on the CPU,
both in float32: the loss of three steps, the first gradient as AdamW
gets it, and the weights' change after the steps, for each kind of
layer the reference covers, under the cells' own optimizer settings.

The cells run AdamW with weight decay 0: at 0.1 the program decays no
layer matrix (``repro.optim.adamw._no_decay`` matches ``pos`` in the
``pos0`` of each layer's path) where the reference decays them all.
PERF.md lists that fault under Open questions."""
import jax
import pytest

from bench import compare
from bench.drivers import train as DT
from bench.tests import tiny

VARIANTS = {
    "layernorm-gated-mha-partial-rotary": tiny.config(dtype="float32"),
    "rmsnorm-plain-gelu-gqa-full-rotary": tiny.config(
        norm="rmsnorm", gated=False, act="gelu_new", kv=2, rot=1.0,
        dtype="float32"),
}


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_reference_follows_the_program(variant):
    cell = tiny.cell("stablelm-1.6b.train-2k", VARIANTS[variant], {})
    devices = jax.devices()[:1]
    tr = DT.Trainer(cell, devices)
    prog = tr.start(2**31 + 11, 3)
    ref = DT.reference_readings(cell.config, cell.spec["optimizer"],
                                2**31 + 11, tr.host_batches[:3], devices)
    numbers = compare.train_numbers(prog, ref)
    # the same arithmetic in float32 on both sides: agreement to round-off
    assert numbers["loss_gap"] < 1e-5, numbers
    assert numbers["grad_gap"] < 1e-4, numbers
    assert numbers["change_gap"] < 1e-4, numbers
    assert len(prog["loss"]) == 3 and prog["loss"][0] > prog["loss"][2]
