"""Compile rehearsals for the TPU: the programs ``chip_smoke.py`` runs,
compiled at their real sizes for a described (not attached) TPU v5e.

The TPU compiler refuses here what the chip would refuse: a program that
does not fit the 16 GiB of device memory, a sharding it cannot partition.
Nothing runs, so these tests say nothing about results or times.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file. All such tests stay in this one file for the same reason.
"""
import dataclasses
import os
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

import chip_smoke as CS
from bench import common as BC
from bench import program as BP
from bench import scopes as BS
from repro.core.trace import LAYERS
from repro.configs import get_config
from repro.launch import mesh as LM
from repro.launch import steps as ST
from repro.optim import adamw as OPT

GiB = 2 ** 30
HBM_BUDGET = 15 * GiB   # of the v5e's 16 GiB, leaving room for the runtime


@pytest.fixture(scope="module")
def topo():
    """A described v5e:2x2 host, with the persistent compilation cache
    off: a TPU program written to it here could not be read back."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"   # else the compiler logs in /tmp
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler installed
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)
        cc.reset_cache()
        if log_dir is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = log_dir


@pytest.fixture(scope="module")
def one_chip(topo):
    return LM.MeshLifecycle(1, 1, 1, 1, devices=topo.devices[:1]).build()


def _sharded(mesh, structs, pspecs):
    return jax.tree.map(
        lambda s, p: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                          sharding=NamedSharding(mesh, p)),
        structs, pspecs)


def _compile_train_step(cfg, mesh, axes, batch=CS.BATCH, seq=CS.SEQ,
                        overdecompose=CS.OVERDECOMPOSE):
    """chip_smoke's train step (at ``batch`` x ``seq``), lowered from
    shapes on ``mesh``; returns (compiled, bytes of the whole unsharded
    state)."""
    topts = ST.TrainOptions(overdecompose=overdecompose,
                            dtype=jnp.bfloat16)
    step_fn, _, _ = ST.make_train_step(cfg, mesh, axes, OPT.AdamWConfig(),
                                       topts)
    (ps, pp), (ss, sp) = ST.state_layouts(cfg, axes, topts)
    batch = {k: jax.ShapeDtypeStruct(st.shape, st.dtype,
                                     sharding=NamedSharding(mesh, spec))
             for k, (st, spec) in ST.batch_struct(
                 cfg, axes, batch, seq, kind="train").items()}
    compiled = step_fn.lower(_sharded(mesh, ps, pp), _sharded(mesh, ss, sp),
                             batch).compile()
    state_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree.leaves((ps, ss)))
    return compiled, state_bytes


def _smoke_config():
    return dataclasses.replace(get_config(CS.ARCH), n_layers=CS.SMOKE_LAYERS)


def test_one_chip_train_step_fits(one_chip):
    mesh, axes = one_chip
    compiled, _ = _compile_train_step(_smoke_config(), mesh, axes)
    ma = compiled.memory_analysis()
    used = ma.argument_size_in_bytes + ma.temp_size_in_bytes
    assert used < HBM_BUDGET, (ma.argument_size_in_bytes,
                               ma.temp_size_in_bytes)


def test_one_chip_attention_runs_the_flash_kernel(one_chip):
    """The benchmark's one-chip cell (stablelm-1.6b-6l, MHA at head dim
    64, 4 x 2048) still fits, its attention core runs the Pallas flash
    kernel, and every kernel call, forward, remat recompute and the dK/dV
    and dQ backward alike, carries the ``attn_core`` layer name that the
    benchmark's device-time readers map (else it reads as unscoped)."""
    mesh, axes = one_chip
    cfg = BP.arch(BC.load_json(BC.ROOT / "bench" / "configs"
                               / "stablelm-1.6b-6l.json"))
    compiled, _ = _compile_train_step(cfg, mesh, axes, batch=4, seq=2048)
    ma = compiled.memory_analysis()
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < HBM_BUDGET
    text = compiled.as_text()
    smap = BS.instruction_layers(text, LAYERS)
    calls = {}
    for body in BS.computations(text).values():
        for name, _, line in body:
            if 'custom_call_target="tpu_custom_call"' in line:
                on = re.search(r'op_name="([^"]*)"', line)
                calls[name] = on.group(1) if on else ""
    assert calls
    assert {smap[n][1] for n in calls} == {"attn_core"}
    kinds = [("forward", lambda t: not t.startswith("jit(step)/transpose")),
             ("recompute", lambda t: "rematted_computation" in t),
             ("dK/dV", lambda t: "bwd_dkv" in t),
             ("dQ", lambda t: "bwd_dq" in t)]
    for kind, test in kinds:
        assert any(test(t) for t in calls.values()), (kind, calls)


def _custom_calls(text):
    """Each TPU custom call of an HLO text -> its ``op_name``."""
    calls = {}
    for body in BS.computations(text).values():
        for name, _, line in body:
            if 'custom_call_target="tpu_custom_call"' in line:
                on = re.search(r'op_name="([^"]*)"', line)
                calls[name] = on.group(1) if on else ""
    return calls


def test_one_chip_moe_cell_runs_its_kernels(one_chip):
    """The benchmark's MoE cell (deepseek-v2-lite-16b-6l-8e at its
    traffic's batch and its cell's options) fits one chip; the MLA core
    runs the flash kernel and no fp32 score tensor of the jnp core, whole
    or per block, is left; the held experts run the grouped-matmul
    kernel, forward, recompute, dX and dW (``gmm``, ``tgmm``); every
    custom call carries the layer name the benchmark maps, ``attn_core``
    or ``experts``."""
    mesh, axes = one_chip
    name = "deepseek-v2-lite-16b.train-moe-8k"
    cell = BC.load_cell(name)
    cfg = BP.arch(cell.config)
    B, T = cell.traffic["global_batch"], cell.traffic["seq_len"]
    compiled, _ = _compile_train_step(
        cfg, mesh, axes, batch=B, seq=T,
        overdecompose=cell.spec["train_options"]["overdecompose"])
    text = compiled.as_text()
    smap = BS.instruction_layers(text, LAYERS)
    calls = _custom_calls(text)
    assert {smap[n][1] for n in calls} == {"attn_core", "experts"}
    experts = [t for n, t in calls.items() if smap[n][1] == "experts"]
    kinds = [("forward", lambda t: "jvp(" in t and "transpose" not in t
              and "rematted" not in t),
             ("recompute", lambda t: "rematted_computation" in t),
             ("dX", lambda t: "transpose" in t and "jit(gmm)" in t),
             ("dW", lambda t: "jit(tgmm)" in t)]
    for kind, test in kinds:
        assert any(test(t) for t in experts), (kind, experts)
    assert any("flash_mha_bwd_dq" in t for t in calls.values())
    # the jnp core's scores: (.., T, T) whole, (.., 512, 1024) per block
    assert not re.search(rf"f32\[[0-9,]*{T},{T}\]", text)
    assert not re.search(r"f32\[[0-9,]*,512,1024\]", text)


def test_one_chip_paged_serve_step_compiles(one_chip):
    """Both row widths the engine runs (a prefill chunk and one decode
    token), in fp32 at the highest matmul precision as chip_smoke serves."""
    mesh, axes = one_chip
    cfg = _smoke_config()
    scfg = CS.SERVE
    build, pspecs = ST.make_paged_step(cfg, mesh, axes, dtype=jnp.float32)
    fn, pools = build(scfg["pages_per_shard"], scfg["page_size"])
    structs, _ = ST.init_model(cfg, axes, abstract=True, dtype=jnp.float32)
    params = _sharded(mesh, structs, pspecs)
    pools = jax.tree.map(
        lambda t: jax.ShapeDtypeStruct(t[0].shape, t[0].dtype,
                                       sharding=NamedSharding(mesh, t[1])),
        pools, is_leaf=lambda t: isinstance(t, tuple) and len(t) == 2
        and isinstance(t[0], jax.ShapeDtypeStruct))
    rep = NamedSharding(mesh, P())
    R, max_pages = scfg["slots"], scfg["pages_per_shard"] - 1

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=rep)

    with jax.default_matmul_precision("highest"):
        for T in (scfg["chunk"], 1):
            compiled = fn.lower(params, pools, i32(R, T), i32(R, T), i32(R),
                                i32(R, max_pages)).compile()
            ma = compiled.memory_analysis()
            assert ma.argument_size_in_bytes + ma.temp_size_in_bytes \
                < HBM_BUDGET


def test_full_depth_train_step_shards_over_four_chips(topo):
    """The 28-layer model, which one chip cannot hold, on the first of
    chip_smoke's four-chip decompositions: each chip's arguments are
    about a quarter of the whole state, and never more than a third."""
    mesh, axes = LM.MeshLifecycle(*CS.DECOMPOSITIONS[0],
                                  devices=topo.devices).build()
    compiled, state_bytes = _compile_train_step(get_config(CS.ARCH), mesh,
                                                axes)
    ma = compiled.memory_analysis()
    assert ma.argument_size_in_bytes <= state_bytes / 3, (
        ma.argument_size_in_bytes, state_bytes)
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < HBM_BUDGET
