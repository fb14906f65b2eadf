"""``attn_core_kernel_share.train`` on a synthetic trace: the custom-call
share of the attention core's device time."""
import pytest

from bench import common as C
from bench import scopes as SC

READER = "attn_core_kernel_share.train"
SMAP = {"custom-call.1": ("custom-call", "attn_core"),
        "fusion.2": ("fusion", "attn_core"),
        "custom-call.3": ("custom-call", None),
        "fusion.4": ("fusion", "mlp"),
        "all-reduce.5": ("all-reduce", "attn_core")}


def test_kernel_share_of_attn_core(monkeypatch):
    monkeypatch.setattr(SC, "run_map", lambda: SMAP)
    ops = {"custom-call.1 bf16[2,32,2048,64]": 3.0,
           "fusion.2 bf16[2,2048,32,64]": 1.0,
           "custom-call.3 f32[8]": 5.0,         # a custom call of no layer
           "fusion.4 bf16[8]": 7.0,             # another layer
           "all-reduce.5 f32[8]": 11.0,         # a collective
           "fusion.6 f32[8]": 13.0}             # not in the map
    ctx = {"trace": {"ops": ops, "window_s": 10.0}}
    assert C.load_reader(READER)(ctx) == pytest.approx(75.0)


@pytest.mark.parametrize("smap,ops", [
    (None, {"custom-call.1 f32[8]": 1.0}),      # no layer names
    (SMAP, {"fusion.4 bf16[8]": 1.0}),          # no attention core time
])
def test_nothing_to_read(monkeypatch, smap, ops):
    monkeypatch.setattr(SC, "run_map", lambda: smap)
    ctx = {"trace": {"ops": ops, "window_s": 10.0}}
    assert C.load_reader(READER)(ctx) is None
    assert C.load_reader(READER)({}) is None


def test_jnp_core_reads_zero(monkeypatch):
    """The attention core as fusions alone (the parent's) reads 0."""
    monkeypatch.setattr(SC, "run_map", lambda: SMAP)
    ctx = {"trace": {"ops": {"fusion.2 bf16[2,2048,32,64]": 1.0},
                     "window_s": 10.0}}
    assert C.load_reader(READER)(ctx) == 0.0
