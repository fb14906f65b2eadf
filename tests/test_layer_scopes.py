"""The layer scopes of core/trace.py in the compiled train step.

Every layer a dense decoder uses names its ops in the optimized HLO's
``op_name`` metadata, in the forward pass and in the backward
(``transpose(…)``) pass alike, under overdecompose 2 and full remat; and
the scopes cost nothing: with metadata taken out, the compiled program is
the one an unscoped build gives.
"""
import contextlib
import dataclasses
import re

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from repro.configs import get_config
from repro.core import trace
from repro.launch import mesh as LM
from repro.launch import steps as ST
from repro.optim import adamw as OPT

#: The layer scopes a dense decoder's train step runs.
DENSE = ("vocab", "norm", "attn", "attn_core", "mlp", "update")

_FRAME_TABLES = re.compile(
    r"^(FileNames|FunctionNames|FileLocations|StackFrames)\n.*?\n\n",
    re.S | re.M)
_METADATA = re.compile(r', metadata=\{(?:[^}"]|"(?:[^"\\]|\\.)*")*\}')
_WRAP = re.compile(r"^\w+\((.*)\)$")


def _train_step_hlo():
    """The compiled train step of a 2-layer dense decoder at tiny widths
    on one device, overdecompose 2, full remat; a fresh trace each call."""
    cfg = dataclasses.replace(get_config("stablelm-1.6b").reduced(),
                              n_layers=2)
    mesh = LM.make_smoke_mesh((1, 1, 1, 1))
    axes = LM.bind_4d(mesh)
    topts = ST.TrainOptions(overdecompose=2, dtype=jnp.float32,
                            remat_policy="full")
    step_fn, _, _ = ST.make_train_step(cfg, mesh, axes, OPT.AdamWConfig(),
                                       topts)
    (ps, pp), (ss, sp) = ST.state_layouts(cfg, axes, topts)

    def sharded(structs, pspecs):
        return jax.tree.map(lambda s, p: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=NamedSharding(mesh, p)),
            structs, pspecs)
    batch = {k: jax.ShapeDtypeStruct(st.shape, st.dtype,
                                     sharding=NamedSharding(mesh, spec))
             for k, (st, spec) in ST.batch_struct(cfg, axes, 4, 16).items()}
    return step_fn.lower(sharded(ps, pp), sharded(ss, sp),
                         batch).compile().as_text()


def _scopes_of(op_name):
    """The components of an ``op_name``, transform wrappers taken off."""
    out = []
    for part in op_name.split(";")[0].split("/"):
        while (m := _WRAP.match(part)):
            part = m.group(1)
        out.append(part)
    return out


def _program(txt):
    """HLO text without metadata and stack-frame tables."""
    return _METADATA.sub("", _FRAME_TABLES.sub("", txt))


def test_dense_layers_named_forward_and_backward():
    names = set(re.findall(r'op_name="((?:[^"\\]|\\.)*)"',
                           _train_step_hlo()))
    for layer in DENSE:
        fwd = [n for n in names
               if layer in _scopes_of(n) and "transpose(" not in n]
        assert fwd, f"no forward op named {layer!r}"
        if layer != "update":
            bwd = [n for n in names
                   if layer in _scopes_of(n) and "transpose(" in n]
            assert bwd, f"no backward op named {layer!r}"


def test_scopes_change_only_metadata(monkeypatch):
    scoped = _train_step_hlo()
    monkeypatch.setattr(trace, "scope",
                        lambda *a, **k: contextlib.nullcontext())
    monkeypatch.setattr(trace, "layer",
                        lambda *a, **k: contextlib.nullcontext())
    plain = _train_step_hlo()
    assert 'attn_core/' in scoped and 'attn_core/' not in plain
    assert "FileNames" not in _program(scoped)
    assert _program(scoped) == _program(plain)
