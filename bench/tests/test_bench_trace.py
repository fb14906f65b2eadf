"""The reduction from a profiler trace to the per-layer numbers, on
synthetic intervals and op events."""
import pytest

from bench import trace_reduce as T


def test_union_subtract_clip():
    u = T.union([(3, 4), (0, 1), (0.5, 2), (5, 5)])
    assert u == [(0, 2), (3, 4)]
    assert T.subtract(u, T.union([(1, 3.5)])) == [(0, 1), (3.5, 4)]
    assert T.clip(u, 1, 3.5) == [(1, 2), (3, 3.5)]
    assert T.total(u) == 3


@pytest.mark.parametrize("text,kind", [
    ("%all-reduce.3 = f32[8]{0} all-reduce(f32[8]{0} %x)", "all-reduce"),
    ("%all-gather-start.1 = (bf16[4]{0}, bf16[8]{0}) "
     "all-gather-start(bf16[4]{0} %p)", "all-gather"),
    ("%collective-permute-done.2 = bf16[4]{0} "
     "collective-permute-done(bf16[4]{0} %c)", "collective-permute"),
    ("%fusion.7 = f32[8]{0} fusion(f32[8]{0} %all-reduce.3), kind=kLoop",
     None),
    ("%reduce-scatter.4 = f32[2]{0} reduce-scatter(f32[8]{0} %g)",
     "reduce-scatter"),
])
def test_collective_kind_reads_the_op_not_its_operands(text, kind):
    assert T.collective_kind(text) == kind


def test_op_label():
    label, control = T.op_label(
        "%fusion.1076 = (f32[2,32]{1,0:T(8,128)S(1)}, f32[2]{0}) "
        "fusion(f32[2]{0} %a), kind=kOutput")
    assert label == "fusion.1076 (f32[2,32], f32[2])" and not control
    assert T.op_label("%while.5 = (s32[]) while((s32[]) %t), "
                      "condition=%c, body=%b")[1]


def _ev(name, op, s, e):
    return (f"%{name} = f32[8]{{0}} {op}(f32[8]{{0}} %x)", s, e)


def test_reduce_two_chips():
    raw = {"devices": {
        0: [_ev("fusion.1", "fusion", 1.0, 3.0),
            _ev("all-reduce.1", "all-reduce", 2.5, 4.0),
            _ev("fusion.2", "fusion", 5.0, 6.0)],
        1: [_ev("fusion.1", "fusion", 1.0, 2.0),
            _ev("all-reduce.1", "all-reduce", 2.0, 3.0)]},
        "host": [("bench.window", 0.0, 10.0), ("step", 0.7, 4.5),
                 ("sync", 6.5, 9.0), ("other", 0.0, 1.0)]}
    red = T.reduce(raw, ["step", "sync"], "bench.window")
    assert red["window_s"] == 10.0
    assert red["busy_s"] == pytest.approx((4.0 + 2.0) / 2)
    assert red["collective_s"] == pytest.approx((1.5 + 1.0) / 2)
    # chip 0: 3.0-4.0 exposed; chip 1: all of 2.0-3.0 exposed
    assert red["collective_exposed_s"] == pytest.approx((1.0 + 1.0) / 2)
    # chip 0's gaps, longest first, each named by the innermost span
    # of the benchmark's own covering its middle: 6-10, 0-1, 4-5
    assert [(n, round(t, 6)) for n, t in red["gaps"]] == [
        ("sync", 4.0), ("no span", 1.0), ("step", 1.0)]
    assert T.busy_within(red["chips"][0], 0.5, 4.5) == pytest.approx(3.0)
    br = T.breakdown(red)
    assert br["device_ops"][0][0].startswith("fusion.1")
    assert len(br["idle_gaps"]) <= 10


def test_reduce_without_window_reads_nothing():
    assert T.reduce({"devices": {0: []}, "host": []}, [], "w") == {}


def _recorded():
    import json
    from pathlib import Path
    d = json.loads((Path(__file__).parent / "data"
                    / "serve_trace_slice.json").read_text())
    raw = {"devices": {int(k): [tuple(e) for e in v]
                       for k, v in d["devices"].items()},
           "host": [tuple(h) for h in d["host"]]}
    names = {n for n, _, _ in raw["host"]}
    return raw, T.reduce(raw, names, "bench.window")


def test_recorded_chip_trace():
    """A slice of a serving window recorded on a v5e chip."""
    raw, red = _recorded()
    assert red["window_s"] == pytest.approx(0.05)
    assert 0.5 * red["window_s"] < red["busy_s"] <= red["window_s"]
    # ops run one at a time on the core: leaving out the control-flow
    # ops, whose events span their bodies' ops, op times add up to busy
    assert sum(red["ops"].values()) == pytest.approx(red["busy_s"],
                                                     rel=1e-3)
    assert not any(label.startswith("while") for label in red["ops"])
    assert red["collective_s"] == 0 and red["collective_exposed_s"] == 0
    idle = sum(t for _, t in red["gaps"])
    assert red["busy_s"] + idle == pytest.approx(red["window_s"], rel=1e-6)
    assert {n for n, _ in red["gaps"]} <= {n for n, _, _ in raw["host"]}
    assert len(T.breakdown(red)["device_ops"]) == 10


def test_device_readers_on_the_recorded_trace():
    from bench.common import load_reader
    _, red = _recorded()
    idle = load_reader("device_idle_share.train")({"trace": red})
    assert idle == pytest.approx(100 * (1 - red["busy_s"] / 0.05))
    # no collective ran on this one chip: nothing to read, never 0
    for name in ("collective_share.train", "collective_exposed_share.train"):
        assert load_reader(name)({"trace": red}) is None
