"""Runtime telemetry (launch/telemetry.py) + named-scope trace
attribution (core/trace.py).

Pins the observability contracts: the JSONL schema round-trips through
its own validator, MFU math agrees with a hand count and with the
roofline's model-flops constant, compiled HLO carries the scope names
for a ring matmul and a ZeRO-3 gather, the scopes change nothing but
metadata against an uninstrumented build, and the drift monitor warns
exactly once per out-of-band excursion.
"""
import contextlib
import json
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from conftest import N_DEVICES
from repro.configs import get_config
from repro.configs.shapes import InputShape
from repro.core import collective_matmul as CMM
from repro.core import comm_model as CM
from repro.core import gradsync as GS
from repro.core import mesh as M
from repro.core import trace
from repro.launch import mesh as LM
from repro.launch import roofline as RL
from repro.launch import telemetry as TL


# --------------------------------------------------------------------- #
# JSONL schema round-trip
# --------------------------------------------------------------------- #

def test_jsonl_schema_roundtrip(tmp_path):
    path = str(tmp_path / "run.jsonl")
    telem = TL.Telemetry("t0", path=path, tokens_per_step=128,
                         flops_per_token=6.0, peak_flops_per_device=1e12,
                         n_devices=2, verbose=False,
                         meta={"arch": "toy", "mesh": "1,1,1,1"})
    for s in range(3):
        rec = telem.train_step(s + 1, 0.01 * (s + 1), loss=1.0 - 0.1 * s,
                               grad_norm=0.5)
        TL.validate_record(rec)
    telem.serve_step(0, 0.002, new_tokens=4, queue_depth=2, active=4,
                     page_util=0.25, preemptions=0, step_kind="mixed")
    telem.close(extra={"note_requests": 4.0})
    n = TL.validate_file(path)
    assert n == 6  # meta + 3 train + 1 serve + summary
    kinds = [json.loads(l)["kind"] for l in open(path)]
    assert kinds == ["meta"] + ["train_step"] * 3 + ["serve_step",
                                                     "summary"]
    summary = json.loads(open(path).readlines()[-1])
    assert summary["steps"] == 4 and summary["note_requests"] == 4.0

    # the validator actually rejects malformed records
    with pytest.raises(ValueError):
        TL.validate_record({"v": TL.SCHEMA_VERSION, "run": "x",
                            "kind": "train_step", "step": 1})
    with pytest.raises(ValueError):
        TL.validate_record({"v": 99, "run": "x", "kind": "meta"})
    with pytest.raises(ValueError):
        TL.validate_record({"v": TL.SCHEMA_VERSION, "run": "x",
                            "kind": "train_step", "step": 1,
                            "step_s": 0.1, "ema_s": 0.1, "tok_s": 10.0,
                            "mfu": "not-a-number"})
    empty = tmp_path / "empty.jsonl"
    empty.write_text("")
    with pytest.raises(ValueError):
        TL.validate_file(str(empty))


# --------------------------------------------------------------------- #
# MFU math
# --------------------------------------------------------------------- #

def test_mfu_hand_count(tmp_path):
    # 6 flops/token * 4 tok/s over 2 devices * 12 flop/s peak => 100%
    telem = TL.Telemetry("t1", path=str(tmp_path / "m.jsonl"),
                         tokens_per_step=4, flops_per_token=6.0,
                         peak_flops_per_device=12.0, n_devices=2,
                         verbose=False)
    assert telem.mfu(4.0) == pytest.approx(1.0)
    assert telem.mfu(1.0) == pytest.approx(0.25)
    rec = telem.train_step(1, 1.0)  # 4 tokens in 1 s
    assert rec["mfu"] == pytest.approx(1.0)
    telem.close()
    # MFU disabled when any constant is missing
    t2 = TL.Telemetry("t2", path=str(tmp_path / "n.jsonl"),
                      tokens_per_step=4, verbose=False)
    assert t2.mfu(4.0) is None
    t2.close()


def test_model_flops_per_token_vs_roofline():
    cfg = get_config("qwen3-1.7b").reduced()
    n_active = float(cfg.active_param_count())
    assert CM.model_flops_per_token(cfg) == pytest.approx(6.0 * n_active)
    assert CM.model_flops_per_token(cfg, "serve") == pytest.approx(
        2.0 * n_active)
    with pytest.raises(ValueError):
        CM.model_flops_per_token(cfg, "prefill")

    # the roofline's per-device model flops divide the SAME constant —
    # telemetry MFU and dryrun useful_ratio share one numerator
    shape = InputShape("t", seq_len=32, global_batch=8, kind="train")
    assert RL.model_flops_per_device(cfg, shape, 4) == pytest.approx(
        6.0 * n_active * 8 * 32 / 4)
    dec = InputShape("d", seq_len=32, global_batch=8, kind="decode")
    assert RL.model_flops_per_device(cfg, dec, 4) == pytest.approx(
        2.0 * n_active * 8 / 4)


# --------------------------------------------------------------------- #
# named scopes in compiled HLO
# --------------------------------------------------------------------- #

def _z_mesh():
    return LM.make_smoke_mesh((1, 1, 2, 4) if N_DEVICES >= 8
                              else (1, 1, 1, 4))


_FRAME_TABLES = re.compile(
    r"\n(FileNames|FunctionNames|FileLocations|StackFrames)\n.*?\n\n", re.S)


def _op_names(txt):
    """The ``op_name`` metadata of every HLO instruction. Scope names are
    matched here and not in the raw text: JAX also writes Python file and
    function names into the HLO (its stack-frame tables), and those
    contain the same words (``_ring_ag_hlo``, ``ag_matmul``)."""
    return set(re.findall(r'op_name="([^"]*)"', txt))


def _has_scope(txt, name):
    return any(name in n for n in _op_names(txt))


_METADATA = re.compile(r', metadata=\{(?:[^}"]|"(?:[^"\\]|\\.)*")*\}')


def _program(txt):
    """HLO text minus its stack-frame tables and every instruction's
    metadata: the tables and ``stack_frame_id``s record the Python call
    sites, and ``op_name`` the scopes, neither of which is the program."""
    return _METADATA.sub("", _FRAME_TABLES.sub("\n", txt))


def _ring_ag_hlo():
    """Fresh jit wrapper every call, so that a patched ``trace.scope``
    is traced anew."""
    mesh = _z_mesh()
    axes = LM.bind_4d(mesh)

    def body(v, w):
        return CMM.ag_matmul(v, w, axes.z)

    f = shard_map(body, mesh=mesh, in_specs=(P(None, None), P(None, "z")),
                  out_specs=P(None, None), check_vma=False)
    v = jnp.ones((4, 8))
    w = jnp.ones((8, 6 * mesh.shape["z"]))
    return jax.jit(f).lower(v, w).compile().as_text()


def test_scopes_in_ring_matmul_hlo():
    txt = _ring_ag_hlo()
    assert _has_scope(txt, "ring_ag[z]/hop0")
    assert _has_scope(txt, "gemm/chunk0")
    assert "collective-permute" in txt


def test_scopes_in_zero3_and_dp_hlo():
    shape = (4, 1, 2, 1) if N_DEVICES >= 8 else (4, 1, 1, 1)
    mesh = LM.make_smoke_mesh(shape)
    axes = LM.bind_4d(mesh)
    structs = {"w": jax.ShapeDtypeStruct((4, 8), jnp.float32),
               "b": jax.ShapeDtypeStruct((8,), jnp.float32)}
    from repro.core.partition import ParamSpec
    specs = {"w": ParamSpec(P(None, None), False),
             "b": ParamSpec(P(None,), False)}
    plan = GS.make_leaf_plan(structs, specs, axes)

    def body(w, b):
        # dict keys flatten sorted: bucket0 <-> "b", bucket1 <-> "w"
        shards = GS.reduce_scatter_grads({"w": w, "b": b}, plan, axes)
        leaf = GS.gather_param_leaf(shards[0], plan.buckets[0], axes)
        return leaf, shards[1]

    f = shard_map(body, mesh=mesh, in_specs=(P(None, None), P(None)),
                  out_specs=(P(None), P("data")), check_vma=False)
    txt = jax.jit(f).lower(jnp.ones((4, 8)), jnp.ones((8,))) \
        .compile().as_text()
    assert _has_scope(txt, "dp_rs/bucket0")
    assert _has_scope(txt, "dp_rs/bucket1")
    assert _has_scope(txt, "zero3_ag[data]/leaf0")


def test_scopes_in_seq_kv_ring_hlo():
    from repro.core.overlap import OverlapConfig
    from repro.layers import attention as A
    p = 4 if N_DEVICES >= 4 else 2
    mesh = LM.make_smoke_mesh((1, 1, 1, 1, p),
                              ("data", "x", "y", "z", "seq"))
    axes = LM.bind_4d(mesh).with_overlap(
        OverlapConfig(ring_attention=True))
    q = jnp.ones((2, 16, 2, 4))
    spec = P(None, "seq", None, None)
    f = shard_map(
        lambda a, b, c: A.seq_attn(a, b, c, axes, causal=True),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    txt = jax.jit(f).lower(q, q, q).compile().as_text()
    assert _has_scope(txt, "ring_exchange[seq]/hop1")


def test_scope_disabled_hlo_byte_identical(monkeypatch):
    """The zero-cost pin: scopes change only metadata. The compiled HLO
    with scopes is, metadata and stack-frame tables left out
    (:func:`_program`), byte-for-byte what an uninstrumented build
    produces (same body, ``scope`` patched to nullcontext, fresh jit
    wrappers so nothing is cached across the comparison)."""
    base = _ring_ag_hlo()
    assert _has_scope(base, "ring_ag[z]/hop0")
    assert _has_scope(base, "gemm/chunk0")

    monkeypatch.setattr(trace, "scope",
                        lambda *a, **k: contextlib.nullcontext())
    stripped = _ring_ag_hlo()
    # the scopes were there and are gone: the comparison is not vacuous
    assert not _has_scope(stripped, "ring_ag[")
    assert not _has_scope(stripped, "gemm/chunk")
    assert base != stripped
    assert _program(base) == _program(stripped)


def test_scope_labels():
    assert trace.label("ring_ag", "z", "hop2") == "ring_ag[z]/hop2"
    assert trace.label("dp_rs", None, "bucket3") == "dp_rs/bucket3"
    assert trace.label("ring_rs", ("data", "z")) == "ring_rs[data+z]"
    assert trace.label("embed_gather", ()) == "embed_gather"


def test_scope_decorator_and_noop():
    calls = []

    @trace.scope("k", None, "d")
    def fn(x):
        calls.append(x)
        return x + 1

    # the decorator calls through, every call, eagerly and when traced
    assert fn(1) == 2 and fn(2) == 3 and calls == [1, 2]
    txt = jax.jit(fn).lower(jnp.ones(3)).as_text(debug_info=True)
    assert "k/d/" in txt
    with pytest.raises(ValueError):
        trace.layer("not_a_layer")


# --------------------------------------------------------------------- #
# drift monitor
# --------------------------------------------------------------------- #

def test_drift_monitor_warns_once_per_excursion():
    mon = TL.DriftMonitor(0.010, band=0.5, min_steps=5)
    # in-band steps: never warns
    for _ in range(6):
        mon.update(0.012)
    assert not mon.out_of_band and mon.check() is None
    # drift out of band (median must cross 1.5x): warn exactly once
    for _ in range(32):
        mon.update(0.020)
    assert mon.out_of_band
    assert mon.check() is not None
    assert mon.check() is None          # second call: already warned
    # back in band resets the latch...
    for _ in range(32):
        mon.update(0.010)
    assert not mon.out_of_band and mon.check() is None
    # ...so the next excursion warns again
    for _ in range(32):
        mon.update(0.005)               # too FAST is also drift
    assert mon.out_of_band and mon.check() is not None

    rec = mon.record(workload="unit")
    for k in ("predicted_s", "measured_p50_s", "ratio", "n"):
        assert isinstance(rec[k], (int, float))
    assert rec["workload"] == "unit" and rec["out_of_band"]

    with pytest.raises(ValueError):
        TL.DriftMonitor(0.0)


def test_drift_below_min_steps_is_silent():
    mon = TL.DriftMonitor(0.010, band=0.5, min_steps=5)
    for _ in range(4):
        mon.update(1.0)                 # wildly off, but too few samples
    assert not mon.out_of_band and mon.check() is None


def test_merge_drift_into_profile():
    from repro.core import calibrate as CB
    prof = CB.CalibrationProfile(
        backend="cpu", n_devices=8, mesh_shape=(2, 2, 2, 1),
        alpha=1e-6, link_bw=5e10, flops=1e12, overlap_efficiency=0.8)
    mon = TL.DriftMonitor(0.010)
    for _ in range(8):
        mon.update(0.018)
    out = CB.merge_drift(prof, mon.record(workload="toy@2,2,2,1"))
    assert out.probes["drift:toy@2,2,2,1"] == pytest.approx(1.8)
    assert out.probes["drift_ratio"] == pytest.approx(1.8)
    assert out.probes["drift_n"] == 8
    # fitted constants are never rescaled by a drift merge
    assert out.alpha == prof.alpha
    assert out.link_bw == prof.link_bw
    assert out.flops == prof.flops
    with pytest.raises(ValueError):
        CB.merge_drift(prof, {"ratio": 1.0})


# --------------------------------------------------------------------- #
# telemetry end-to-end against a real (tiny) engine run
# --------------------------------------------------------------------- #

def test_serve_telemetry_agrees_with_stats(tmp_path):
    """serve_step records + close(extra=stats) must leave a file whose
    summary quotes the engine's own tokens/s (the CSV/JSONL agreement
    satellite)."""
    path = str(tmp_path / "serve.jsonl")
    telem = TL.Telemetry("srv", path=path, verbose=False)
    total = 0
    for s in range(5):
        telem.serve_step(s, 0.001, new_tokens=3, queue_depth=1,
                         active=3, page_util=0.5, preemptions=0)
        total += 3
    engine_tok_s = 1234.5
    telem.close(extra={"tok_s": engine_tok_s, "tokens": total,
                       "steps": 5})
    TL.validate_file(path)
    summary = json.loads(open(path).readlines()[-1])
    assert summary["tok_s"] == engine_tok_s
    assert summary["tokens"] == total == telem.serve_tokens
