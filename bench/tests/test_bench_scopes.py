"""Device time by model layer: the ``op_name`` -> layer rules, the map of
a compiled step's instructions, and the ``device_share.*`` readers on a
synthetic trace."""
import re
import sys

import jax
import jax.numpy as jnp
import pytest

from bench import common as C
from bench import scopes as SC
from bench.drivers import train as DT
from bench.tests import tiny
from repro.core.trace import LAYERS

READERS = ["vocab", "norm", "attn", "attn_core", "mlp", "update",
           "unscoped"]


@pytest.mark.parametrize("op_name,layer", [
    ("jit(step)/jvp()/while/body/closed_call/mlp/dot_general", "mlp"),
    # backward and remat recompute: wrappers come off
    ("jit(step)/transpose(jvp(vocab))/scatter-add", "vocab"),
    ("jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/norm/rsqrt", "norm"),
    # the innermost layer wins
    ("jit(step)/jvp()/while/body/closed_call/attn/attn_core/"
     "bqhgd,bkhd->bhgqk/dot_general", "attn_core"),
    ("jit(step)/jvp()/while/body/closed_call/attn/mul", "attn"),
    # several names joined: the first counts
    ("jit(step)/update/mul;jit(step)/jvp(vocab)/add", "update"),
    ("jit(step)/jvp()/add;jit(step)/update/mul", None),
    # a ring scope inside a layer is no layer of its own
    ("jit(step)/jvp(vocab)/embed_gather[z]/all_gather", "vocab"),
    # a function's name is not a scope, nor a part of a name
    ("jit(update)/add", None),
    ("jit(step)/attn_core_chunked/mul", None),
    ("jit(step)/transpose(jvp())/while/body/dynamic_update_slice", None),
    ("", None),
])
def test_layer_of(op_name, layer):
    assert SC.layer_of(op_name, LAYERS) == layer


HLO = """HloModule m, entry_computation_layout={()->f32[]}

%fused_computation.1 (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  %dot.3 = f32[8]{0} multiply(%p0, %p0), metadata={op_name="jit(step)/jvp()/while/body/closed_call/mlp/dot_general"}
  ROOT %dynamic-update-slice.4 = f32[8]{0} dynamic-update-slice(%dot.3, %p0), metadata={op_name="jit(step)/transpose(jvp())/while/body/dynamic_update_slice"}
}

ENTRY %main.9 (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %fusion.1 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/transpose(jvp())/while/body/dynamic_update_slice"}
  %fusion.2 = f32[8]{0} fusion(%fusion.1), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(step)/jvp()/while/body/closed_call/attn/attn_core/exp"}
  %all-gather-start.5 = (f32[8]{0}, f32[16]{0}) all-gather-start(%fusion.2), metadata={op_name="jit(step)/jvp()/while/body/closed_call/mlp/all_gather"}
  %copy.6 = f32[8]{0} copy(%fusion.2)
  ROOT %add.7 = f32[8]{0} add(%copy.6, %copy.6), metadata={op_name="jit(step)/update/add"}
}
"""


def _smap():
    return SC.instruction_layers(HLO, LAYERS)


def test_instruction_layers():
    m = _smap()
    # own name first; a fusion rooted in plumbing takes its fused layer
    assert m["fusion.2"] == ("fusion", "attn_core")
    assert m["fusion.1"] == ("fusion", "mlp")
    assert m["all-gather-start.5"] == ("all-gather-start", "mlp")
    assert m["copy.6"] == ("copy", None)
    assert m["add.7"] == ("add", "update")
    assert m["dynamic-update-slice.4"][1] is None


def test_reader_on_a_synthetic_trace(monkeypatch, capsys):
    ops = {"fusion.1 f32[8]": 1.0, "fusion.2 f32[8]": 2.0,
           "all-gather-start.5 (f32[8], f32[16])": 4.0,
           "copy.6 f32[8]": 0.5, "add.7 f32[8]": 0.25,
           "fusion.99 f32[8]": 0.25}
    monkeypatch.setattr(SC, "run_map", _smap)
    ctx = {"trace": {"ops": ops, "window_s": 10.0}}
    read = {k: C.load_reader(f"device_share.{k}.train")(ctx)
            for k in READERS}
    assert read["mlp"] == pytest.approx(10.0)
    assert read["attn_core"] == pytest.approx(20.0)
    assert read["update"] == pytest.approx(2.5)
    # the collective is left out; a copy and an op not in the map count
    # as unscoped
    assert read["unscoped"] == pytest.approx(7.5)
    assert read["vocab"] == read["norm"] == read["attn"] == 0.0
    assert sum(read.values()) == pytest.approx(40.0)
    err = capsys.readouterr().err
    assert "unscoped copy.6 f32[8]" in err and "fusion.2" not in err


def test_no_layer_names_read_nothing(monkeypatch, capsys):
    txt = jax.jit(lambda x: jnp.sin(x) @ x).lower(
        jnp.ones((4, 4))).compile().as_text()
    assert SC.scope_map(txt, LAYERS) is None
    assert "no instruction" in capsys.readouterr().err
    monkeypatch.setattr(SC, "run_map", lambda: None)
    ctx = {"trace": {"ops": {"dot.1 f32[4,4]": 1.0}, "window_s": 2.0}}
    for k in READERS:
        assert C.load_reader(f"device_share.{k}.train")(ctx) is None
    # a program without layer names (before they were added): nothing
    # is compiled
    monkeypatch.undo()
    monkeypatch.setattr(SC, "program_layers", lambda: None)
    monkeypatch.setattr(DT, "Trainer", None)
    assert SC.run_map.__wrapped__() is None
    assert "names no layers" in capsys.readouterr().err


#: What ops that carry no layer name may run: moving, slicing and
#: converting data and counting loops. No product, transcendental,
#: gather, scatter or reduction.
_HEAVY = {"dot", "convolution", "exponential", "log", "rsqrt", "sqrt",
          "divide", "power", "tanh", "logistic", "gather", "scatter",
          "sort", "reduce"}
_CALLED = re.compile(r"(?:body|condition|true_computation|"
                     r"false_computation)=%?([\w.\-]+)|"
                     r"branch_computations=\{([^}]*)\}")


def _executed(txt, comps):
    """Computations whose instructions run as ops of their own: the
    entry, and the loop bodies and branches it reaches."""
    todo = [re.search(r"^ENTRY %?([\w.\-]+)", txt, re.M).group(1)]
    seen = set()
    while todo:
        c = todo.pop()
        if c in seen:
            continue
        seen.add(c)
        for _, _, line in comps[c]:
            for m in _CALLED.finditer(line):
                names = [m.group(1)] if m.group(1) else re.findall(
                    r"%?([\w.\-]+)", m.group(2))
                todo.extend(names)
    return seen


def test_tiny_step_maps_every_instruction(monkeypatch, capsys):
    cell = tiny.cell("stablelm-1.6b.train-2k",
                     tiny.config("layernorm-gated-mha-partial-rotary"),
                     {"loss_gap": 1, "grad_gap": 1, "change_gap": 1})
    txt = DT.Trainer(cell, jax.devices()[:1]).step.as_text()
    # the readers' map: the step of the command line's cell, compiled
    # again, maps as this one does
    monkeypatch.setattr(sys, "argv", ["run.py", "--workload", cell.name,
                                      "--seed", "1", "--trace", "1"])
    monkeypatch.setattr(C, "load_cell", {cell.name: cell}.__getitem__)
    monkeypatch.setattr(C, "require_devices",
                        lambda n: jax.devices()[:n])
    smap = SC.run_map.__wrapped__()
    assert smap == SC.scope_map(txt, LAYERS)
    assert "step compiled again" in capsys.readouterr().err
    comps = SC.computations(txt)
    assert len(smap) == len(re.findall(r"^\s*(?:ROOT )?%?[\w.\-]+ = ",
                                       txt, re.M))
    used = {layer for _, layer in smap.values()}
    assert {"vocab", "norm", "attn", "attn_core", "mlp", "update"} <= used
    fused = {}
    for body in comps.values():
        for name, opcode, line in body:
            m = re.search(r"calls=%?([\w.\-]+)", line)
            if opcode == "fusion" and m:
                fused[name] = m.group(1)
    for c in _executed(txt, comps):
        for name, opcode, line in comps[c]:
            if smap[name][1] or SC.is_collective(name, opcode):
                continue
            runs = {opcode} | {o for _, o, _ in comps.get(fused.get(name),
                                                          [])}
            assert not runs & _HEAVY, (name, runs)
