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

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

import chip_smoke as CS
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


def _compile_train_step(cfg, mesh, axes):
    """chip_smoke's train step, lowered from shapes on ``mesh``; returns
    (compiled, bytes of the whole unsharded state)."""
    topts = ST.TrainOptions(overdecompose=CS.OVERDECOMPOSE,
                            dtype=jnp.bfloat16)
    step_fn, _, _ = ST.make_train_step(cfg, mesh, axes, OPT.AdamWConfig(),
                                       topts)
    (ps, pp), (ss, sp) = ST.state_layouts(cfg, axes, topts)
    batch = {k: jax.ShapeDtypeStruct(st.shape, st.dtype,
                                     sharding=NamedSharding(mesh, spec))
             for k, (st, spec) in ST.batch_struct(
                 cfg, axes, CS.BATCH, CS.SEQ, kind="train").items()}
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
