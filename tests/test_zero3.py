"""ZeRO-3 param-shard streaming (core/gradsync.py make_leaf_plan /
ParamStreamer + the zero3 train-step path).

The streaming schedule must be a pure decomposition of the replicated
one: params sharded 1/G_data with per-layer just-in-time ring gathers
(and their autodiff-transpose reduce-scatters) match the blocking
psum + replicated-AdamW baseline — bitwise on exactly-summable values,
within fp32 reassociation on a real model. The compiled step must keep
every data-axis gather inside the per-layer streaming window (no
full-parameter all-gather), per-rank param+optimizer state must shrink
by ~G_data, checkpoints must round-trip across different g_data, and
the cross-step comm model must reduce exactly to the PR-3 exposed model
when the window is off. Shapes scale to 4-device CI hosts.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from conftest import N_DEVICES
from repro.core import comm_model as CM
from repro.core import gradsync as GS
from repro.core import mesh as M
from repro.core.gradsync import GradSyncConfig
from repro.core.partition import ParamSpec, spec_tree_to_pspecs
from repro.launch import mesh as LM
from repro.launch import roofline as RL
from repro.launch import steps as ST
from repro.optim import adamw as OPT

SHAPE_2X2 = (2, 2, 1, 1)
SHAPE_DP4 = (4, 1, 2, 1) if N_DEVICES >= 8 else (4, 1, 1, 1)


def _exact_random(key, shape):
    return jax.random.randint(key, shape, -4, 5).astype(jnp.float32)


# --------------------------------------------------------------------- #
# synthetic tree with a scan-stacked leaf
# --------------------------------------------------------------------- #

N_LAYERS = 3


def _toy_tree():
    def leaf(shape, spec, z_reduced=False, y_reduce=False):
        return (jax.ShapeDtypeStruct(shape, jnp.float32),
                ParamSpec(spec, z_reduced, y_reduce))
    tree = {
        "embed": leaf((16, 4), P(None, None)),
        "segments": {"seg0": {
            "w": leaf((N_LAYERS, 8, 4), P(None, "x", None)),
            "norm": leaf((N_LAYERS, 9), P(None, None)),
        }},
        "final_norm": leaf((7,), P()),
    }
    structs = jax.tree.map(lambda t: t[0], tree,
                           is_leaf=lambda t: isinstance(t, tuple))
    specs = jax.tree.map(lambda t: t[1], tree,
                         is_leaf=lambda t: isinstance(t, tuple))
    return structs, specs


def _toy_values(structs, seed=0):
    leaves, treedef = jax.tree.flatten(structs)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return jax.tree.unflatten(
        treedef, [_exact_random(k, l.shape) for k, l in zip(keys, leaves)])


def _stack_of(path, local_shape):
    keys = [str(getattr(k, "key", getattr(k, "name", ""))) for k in path]
    if keys and keys[0] == "segments" and len(local_shape) > 0:
        return int(local_shape[0])
    return 1


def _leaf_plan(structs, specs, axes):
    return GS.make_leaf_plan(structs, specs, axes,
                             no_decay=OPT._no_decay, stack_of=_stack_of)


# --------------------------------------------------------------------- #
# leaf plan structure
# --------------------------------------------------------------------- #

def test_leaf_plan_structure():
    mesh = LM.make_smoke_mesh(SHAPE_2X2)
    axes = LM.bind_4d(mesh)
    structs, specs = _toy_tree()
    plan = _leaf_plan(structs, specs, axes)
    flat, _ = jax.tree_util.tree_flatten_with_path(structs)
    assert len(plan.buckets) == plan.n_leaves == len(flat)
    spec_leaves = jax.tree.leaves(
        specs, is_leaf=lambda s: isinstance(s, ParamSpec))
    for i, (b, (path, leaf)) in enumerate(zip(plan.buckets, flat)):
        assert len(b.segments) == 1 and b.segments[0].leaf == i
        assert b.padded % plan.dp == 0 and b.padded >= b.size
        lshape = GS._local_shape(tuple(leaf.shape),
                                 tuple(spec_leaves[i].spec), axes)
        if _stack_of(path, lshape) > 1:
            assert b.stack == lshape[0]
            assert b.segments[0].shape == lshape[1:]
        else:
            assert b.stack == 1 and b.segments[0].shape == lshape
    # the shard tree keeps the params' own structure
    shard_structs = GS.abstract_param_shards(plan, axes)
    assert (jax.tree.structure(shard_structs)
            == jax.tree.structure(structs))
    # stacked leaves keep their scan dim, flat dims tile over the mesh
    g = axes.size(axes.all_names())
    seg = shard_structs["segments"]["seg0"]["w"]
    assert seg.shape[0] == N_LAYERS and seg.shape[1] % g == 0
    pspecs = GS.param_shard_pspecs(plan, axes)
    assert tuple(pspecs["segments"]["seg0"]["w"])[0] is None


def test_prefetch_requires_zero3():
    with pytest.raises(ValueError, match="zero3"):
        GradSyncConfig(prefetch=True)
    assert GradSyncConfig(zero3=True).enabled
    assert GradSyncConfig(zero3=True).state_sharded
    assert not GradSyncConfig(bucketed=True).state_sharded


# --------------------------------------------------------------------- #
# shard / gather round trip (bitwise)
# --------------------------------------------------------------------- #

@pytest.mark.parametrize("ring", [True, False], ids=["ring", "blocking"])
def test_shard_gather_roundtrip(ring):
    mesh = LM.make_smoke_mesh(SHAPE_DP4)
    axes = LM.bind_4d(mesh)
    structs, specs = _toy_tree()
    plan = _leaf_plan(structs, specs, axes)
    pspecs = spec_tree_to_pspecs(specs)

    def body(params):
        shards = GS.shard_params(params, plan, axes)
        back = GS.unshard_params(shards, plan, axes, ring=ring)
        # a scan-sliced slot row gathers to exactly that layer's params
        slot = jax.tree.map(lambda x: x[1],
                            shards["segments"]["seg0"])
        bt = GS.ParamStreamer(plan=plan, axes=axes,
                              ring=ring).buckets_like()
        row = jax.tree.map(
            lambda s, b: GS.gather_param_leaf(s, b, axes, ring=ring),
            slot, bt["segments"]["seg0"])
        return back, row

    params = _toy_values(structs, seed=3)
    out, row = jax.jit(shard_map(
        body, mesh=mesh, in_specs=(pspecs,),
        out_specs=(pspecs, jax.tree.map(lambda x: P(*tuple(x)[1:]),
                                        pspecs["segments"]["seg0"],
                                        is_leaf=lambda x: isinstance(x, P))),
        check_vma=False))(params)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for k in ("w", "norm"):
        np.testing.assert_array_equal(
            np.asarray(row[k]),
            np.asarray(params["segments"]["seg0"][k][1]))


# --------------------------------------------------------------------- #
# full train step: parity, HLO window, memory
# --------------------------------------------------------------------- #

def _model_setup(shape, gs, *, overdecompose=2, arch="stablelm-1.6b"):
    from repro.configs import get_config
    from repro.optim.adamw import AdamWConfig, init_state

    mesh = LM.make_smoke_mesh(shape)
    axes = LM.bind_4d(mesh)
    cfg = get_config(arch).reduced()
    params, specs = ST.init_model(cfg, axes, jax.random.PRNGKey(0),
                                  dtype=jnp.float32)
    params = ST.device_put_tree(mesh, params, spec_tree_to_pspecs(specs))
    opts = ST.TrainOptions(overdecompose=overdecompose, dtype=jnp.float32,
                           gradsync=gs)
    fn, _, _ = ST.make_train_step(
        cfg, mesh, axes, AdamWConfig(lr=1e-3, warmup_steps=1,
                                     total_steps=50), opts)
    if gs.state_sharded:
        tools = ST.make_gradsync_tools(cfg, mesh, axes, opts)
        state = tools.init(params)
        if gs.zero3:
            params = tools.shard_params(params)
    else:
        tools, state = None, init_state(params)
    rng = np.random.RandomState(0)
    batch = {"tokens": jnp.asarray(rng.randint(0, cfg.vocab_size, (8, 32)),
                                   jnp.int32),
             "labels": jnp.asarray(rng.randint(0, cfg.vocab_size, (8, 32)),
                                   jnp.int32)}
    return cfg, mesh, axes, opts, fn, params, state, batch, tools


ZERO3_MODES = [
    ("zero3", GradSyncConfig(zero3=True, bucket_mb=0.25)),
    ("zero3_prefetch", GradSyncConfig(zero3=True, prefetch=True,
                                      bucket_mb=0.25)),
    ("zero3_noring", GradSyncConfig(zero3=True, ring=False)),
    ("zero3_od1", GradSyncConfig(zero3=True)),  # single microbatch
]


def test_zero3_train_step_parity():
    results = {}
    modes = ([("base", GradSyncConfig(), 2), ("base_od1",
              GradSyncConfig(), 1)]
             + [(n, g, 1 if n == "zero3_od1" else 2)
                for n, g in ZERO3_MODES])
    for name, gs, od in modes:
        _, _, _, _, fn, params, state, batch, tools = _model_setup(
            SHAPE_2X2, gs, overdecompose=od)
        p, s = params, state
        for _ in range(3):
            p, s, m = fn(p, s, batch)
        if gs.zero3:
            p = tools.unshard_params(p)
        results[name] = (float(m["loss"]), float(m["grad_norm"]),
                         [np.asarray(x) for x in jax.tree.leaves(p)])
    for name, _ in ZERO3_MODES:
        # compare against the SAME overdecompose's replicated baseline
        # (od changes fp32 accumulation order on its own)
        lb, nb, pb = results["base_od1" if name == "zero3_od1"
                             else "base"]
        l, n, pz = results[name]
        assert abs(l - lb) < 1e-5, (name, l, lb)
        assert abs(n - nb) < 1e-4 * max(1.0, nb), (name, n, nb)
        gap = max(float(np.max(np.abs(a - b))) for a, b in zip(pb, pz))
        # fp32 reassociation only: the streamed programs fuse FMAs
        # differently (prefetch additionally runs its last layer outside
        # the scan), and the drift compounds over the 3 steps
        assert gap < 2e-5, f"{name}: params diverged from baseline: {gap}"


def test_zero3_n1_segment_parity():
    """Segments with n_periods == 1 (deepseek's dense head segment, and
    EVERY segment of the dry-run depth probes) plan as unstacked: their
    single layer is resident, not streamed, and the scan must not
    re-gather it (regression: the first cut double-gathered and died at
    trace time on any heterogeneous-depth config)."""
    results = {}
    for name, gs in [("base", GradSyncConfig()),
                     ("zero3", GradSyncConfig(zero3=True)),
                     ("zero3_pref", GradSyncConfig(zero3=True,
                                                   prefetch=True))]:
        _, _, _, _, fn, params, state, batch, tools = _model_setup(
            SHAPE_2X2, gs, overdecompose=1, arch="deepseek-v2-lite-16b")
        p, s = params, state
        for _ in range(2):
            p, s, m = fn(p, s, batch)
        results[name] = (float(m["loss"]), float(m["grad_norm"]))
    for name in ("zero3", "zero3_pref"):
        assert abs(results[name][0] - results["base"][0]) < 1e-5, results
        assert abs(results[name][1] - results["base"][1]) < 1e-4 * max(
            1.0, results["base"][1]), results


def test_zero3_unrolled_parity():
    """The python-unrolled layer path (what the dry-run depth probes
    lower) must match the scanned zero3 step: same gather-inside-remat /
    prefetch-retention schedules, python loop instead of scan."""
    from repro.configs import get_config
    from repro.optim.adamw import AdamWConfig, init_state

    def run(gs, unroll):
        mesh = LM.make_smoke_mesh(SHAPE_2X2)
        axes = LM.bind_4d(mesh)
        cfg = get_config("stablelm-1.6b").reduced()
        params, specs = ST.init_model(cfg, axes, jax.random.PRNGKey(0),
                                      dtype=jnp.float32)
        params = ST.device_put_tree(mesh, params,
                                    spec_tree_to_pspecs(specs))
        opts = ST.TrainOptions(overdecompose=1, dtype=jnp.float32,
                               gradsync=gs, unroll_layers=unroll)
        fn, _, _ = ST.make_train_step(
            cfg, mesh, axes, AdamWConfig(lr=1e-3, warmup_steps=1,
                                         total_steps=50), opts)
        if gs.zero3:
            tools = ST.make_gradsync_tools(cfg, mesh, axes, opts)
            state = tools.init(params)
            params = tools.shard_params(params)
        else:
            state = init_state(params)
        rng = np.random.RandomState(0)
        batch = {"tokens": jnp.asarray(
            rng.randint(0, cfg.vocab_size, (8, 32)), jnp.int32),
            "labels": jnp.asarray(
            rng.randint(0, cfg.vocab_size, (8, 32)), jnp.int32)}
        p, s = params, state
        for _ in range(2):
            p, s, m = fn(p, s, batch)
        return float(m["loss"]), float(m["grad_norm"])

    base = run(GradSyncConfig(), True)
    got = run(GradSyncConfig(zero3=True, prefetch=True), True)
    assert abs(got[0] - base[0]) < 1e-5, (got, base)
    assert abs(got[1] - base[1]) < 1e-4 * max(1.0, base[1]), (got, base)


def test_zero3_hlo_streaming_window():
    """No data-axis gradient all-reduce survives, and no data-axis
    all-gather/ring hop moves more than one gathered unit of the leaf
    plan — i.e. no full-parameter all-gather outside the streamed
    per-layer window (the satellite HLO assertion)."""
    dp = SHAPE_DP4[0]
    gs = GradSyncConfig(zero3=True)
    _, _, _, _, fn, params, state, batch, tools = _model_setup(
        SHAPE_DP4, gs)
    hlo = fn.lower(params, state, batch).compile().as_text()
    ops = RL.parse_collective_ops(hlo)
    big_dp_ar = [op for op in ops if op.kind == "all-reduce"
                 and op.group_size == dp and op.raw_bytes > 2048]
    assert not big_dp_ar, "DP gradient all-reduces survived zero3"
    plan = tools.plan
    unit = max(b.padded * jnp.dtype(b.dtype).itemsize
               for b in plan.buckets)
    total = sum(b.padded * b.stack * jnp.dtype(b.dtype).itemsize
                for b in plan.buckets)
    assert unit < total / 2  # the bound is meaningfully tighter
    offenders = [op for op in ops
                 if op.kind in ("all-gather", "collective-permute")
                 and op.raw_bytes > unit]
    assert not offenders, \
        [(o.kind, o.group_size, o.raw_bytes) for o in offenders[:5]]
    assert any(op.kind == "collective-permute" for op in ops)


def test_zero3_state_memory_sharded_by_dp():
    """Per-rank persistent param+optimizer bytes under zero3 are the
    replicated layout's divided by G_data (+ bounded padding slack) —
    the acceptance-bound accounting the dry-run records report."""
    from repro.configs import get_config
    mesh = LM.make_smoke_mesh(SHAPE_DP4)
    axes = LM.bind_4d(mesh)
    cfg = get_config("stablelm-1.6b").reduced()
    base = ST.TrainOptions(dtype=jnp.float32)
    z3 = ST.TrainOptions(dtype=jnp.float32,
                         gradsync=GradSyncConfig(zero3=True))

    def bytes_per_rank(opts):
        (pst, pps), (ost, ops) = ST.state_layouts(cfg, axes, opts)
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

        def tree_bytes(structs, pspecs):
            total = 0
            fs = jax.tree.leaves(structs)
            fp = jax.tree.leaves(pspecs,
                                 is_leaf=lambda x: isinstance(x, P))
            for st, sp in zip(fs, fp):
                div = 1
                for e in tuple(sp):
                    if e is None:
                        continue
                    for nm in (e if isinstance(e, tuple) else (e,)):
                        div *= sizes.get(nm, 1)
                n = int(np.prod(st.shape)) if st.shape else 1
                total += (n // div) * jnp.dtype(st.dtype).itemsize
            return total
        return tree_bytes(pst, pps) + tree_bytes(ost, ops)

    rep, shard = bytes_per_rank(base), bytes_per_rank(z3)
    dp = SHAPE_DP4[0]
    # padding slack: one dp-block of fp32 per (m, v, master, param) leaf
    axes2 = axes.with_overlap(z3.overlap)
    structs, specs = ST.init_model(cfg, axes2, abstract=True,
                                   dtype=jnp.float32)
    plan = ST._zero3_plan(structs, specs, axes2)
    slack = 4 * 4 * sum(b.stack * dp for b in plan.buckets)
    assert shard <= rep / dp + slack, (shard, rep, dp, slack)


# --------------------------------------------------------------------- #
# checkpoint round-trip across g_data (bitwise resumed step)
# --------------------------------------------------------------------- #

def test_zero3_checkpoint_roundtrip_across_gdata(tmp_path):
    """Save the zero3 run (params + state in the replicated layout)
    under g_data=2, restore under g_data=4, and bitwise-compare the
    resumed step against staying on the source mesh. The toy loss runs
    through gather_param_leaf, so the gradient arrives through the
    gather's transpose (the real streaming path); exact small-int
    values make every reduction order exact."""
    from repro.checkpoint import ckpt

    structs, specs = _toy_tree()
    cfg = OPT.AdamWConfig(lr=1e-2, warmup_steps=1, total_steps=10)
    path = os.path.join(tmp_path, "zero3.npz")
    meshes = {"A": LM.make_smoke_mesh(SHAPE_2X2),
              "B": LM.make_smoke_mesh((4, 1, 1, 1))}
    env = {}
    for k, mesh in meshes.items():
        axes = LM.bind_4d(mesh)
        plan = _leaf_plan(structs, specs, axes)
        pspecs = spec_tree_to_pspecs(specs)
        sspecs = GS.sharded_state_pspecs(plan, axes)
        ppspecs = GS.param_shard_pspecs(plan, axes)
        fullspecs = OPT.state_pspecs(pspecs)
        tools = {
            "init": jax.jit(shard_map(
                lambda p, _pl=plan, _ax=axes: GS.init_sharded_state(
                    p, _pl, _ax), mesh=mesh, in_specs=(pspecs,),
                out_specs=sspecs, check_vma=False)),
            "shard_p": jax.jit(shard_map(
                lambda p, _pl=plan, _ax=axes: GS.shard_params(
                    p, _pl, _ax), mesh=mesh, in_specs=(pspecs,),
                out_specs=ppspecs, check_vma=False)),
            "unshard_p": jax.jit(shard_map(
                lambda s, _pl=plan, _ax=axes: GS.unshard_params(
                    s, _pl, _ax), mesh=mesh, in_specs=(ppspecs,),
                out_specs=pspecs, check_vma=False)),
            "gather": jax.jit(shard_map(
                lambda s, _pl=plan, _ax=axes: GS.gather_sharded_state(
                    s, _pl, _ax), mesh=mesh, in_specs=(sspecs,),
                out_specs=fullspecs, check_vma=False)),
            "scatter": jax.jit(shard_map(
                lambda s, _pl=plan, _ax=axes: GS.scatter_full_state(
                    s, _pl, _ax), mesh=mesh, in_specs=(fullspecs,),
                out_specs=sspecs, check_vma=False)),
        }
        env[k] = (mesh, axes, plan, pspecs, sspecs, ppspecs, tools)

    def step_fn(mesh, axes, plan, pspecs, sspecs, ppspecs):
        bt_order = [None] * plan.n_leaves
        for b in plan.buckets:
            bt_order[b.segments[0].leaf] = b
        btree = jax.tree.unflatten(plan.treedef, bt_order)

        def body(shards, state, gbase):
            dp = float(axes.dp)

            def loss(sh):
                full = jax.tree.map(
                    lambda s, b: GS.gather_param_leaf(s, b, axes),
                    sh, btree)
                tot = 0.0
                for w, g in zip(jax.tree.leaves(full),
                                jax.tree.leaves(gbase)):
                    tot = tot + jnp.sum(w * g)
                return tot / dp  # per-rank partials: global grad is
                # mesh-independent (the transpose RS sums dp copies)
            g_sh = jax.grad(loss)(shards)
            gl = [g.astype(jnp.float32) for g in jax.tree.leaves(g_sh)]
            gl = GS.tensor_reduce_shards(gl, plan, axes)
            p, s, _ = OPT.apply_updates_sharded(gl, state, plan, axes,
                                                cfg, rebuild=False)
            return p, s
        return jax.jit(shard_map(body, mesh=mesh,
                                 in_specs=(ppspecs, sspecs, pspecs),
                                 out_specs=(ppspecs, sspecs),
                                 check_vma=False))

    params = _toy_values(structs, seed=1)
    gbase = _toy_values(structs, seed=2)

    mesh, axes, plan, pspecs, sspecs, ppspecs, T = env["A"]
    step_a = step_fn(mesh, axes, plan, pspecs, sspecs, ppspecs)
    pa, sa = step_a(T["shard_p"](params), T["init"](params), gbase)
    ckpt.save_sharded(path, jax.tree.map(np.asarray, T["unshard_p"](pa)),
                      sa, T["gather"], step=1, extra={"zero3": True})
    pa2, sa2 = step_a(pa, sa, gbase)
    ref_p = jax.device_get(T["unshard_p"](pa2))
    ref_s = jax.device_get(T["gather"](sa2))

    mesh, axes, plan, pspecs, sspecs, ppspecs, T = env["B"]
    like_state = {"opt": jax.tree.map(
        lambda s: {k: jax.ShapeDtypeStruct(s.shape, jnp.float32)
                   for k in ("m", "v", "master")}, structs),
        "step": jax.ShapeDtypeStruct((), jnp.int32)}
    full_p, sb, step = ckpt.restore_sharded(path, structs, like_state,
                                            T["scatter"])
    assert step == 1
    pb = T["shard_p"](jax.tree.map(jnp.asarray, full_p))
    pb2, sb2 = step_fn(mesh, axes, plan, pspecs, sspecs, ppspecs)(
        pb, sb, gbase)
    res_p = jax.device_get(T["unshard_p"](pb2))
    res_s = jax.device_get(T["gather"](sb2))
    for a, b in zip(jax.tree.leaves(ref_p), jax.tree.leaves(res_p)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(ref_s), jax.tree.leaves(res_s)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# --------------------------------------------------------------------- #
# comm model: zero3 volume/time + the cross-step window
# --------------------------------------------------------------------- #

LAYERS = CM.transformer_layers(256, 2)
D = CM.Decomposition(4, 2, 2, 2)
TOKENS = 4096


def test_zero3_volume_formulas():
    buf = 120.0
    gsv = CM.gather_or_scatter_volume(4, buf)
    z3 = GradSyncConfig(zero3=True)
    z3p = GradSyncConfig(zero3=True, prefetch=True)
    # per microbatch: fwd AG + bwd re-gather AG + RS (2 with prefetch)
    assert CM.dp_sync_volume(4, buf, z3, 1) == pytest.approx(3 * gsv)
    assert CM.dp_sync_volume(4, buf, z3, 2) == pytest.approx(6 * gsv)
    assert CM.dp_sync_volume(4, buf, z3p, 2) == pytest.approx(4 * gsv)
    # prefetch at one microbatch: AG + RS == the all-reduce floor
    assert CM.dp_sync_volume(4, buf, z3p, 1) == \
        pytest.approx(CM.allreduce_volume(4, buf))
    assert CM.dp_sync_volume(1, buf, z3, 3) == 0.0


def test_zero3_time_conservation_and_hiding():
    gs = GradSyncConfig(zero3=True)
    hw0 = CM.HardwareParams(alpha=0.0)
    st = CM.predict_step_time(LAYERS, TOKENS, D, hw0, gradsync=gs,
                              microbatches=2)
    vol = CM.model_volume(LAYERS, TOKENS, D, gradsync=gs, microbatches=2)
    # α=0 conservation: hiding re-buckets time, it does not destroy it
    assert st.exposed_comm + st.hidden_comm == pytest.approx(
        vol * hw0.bytes_per_elem / hw0.link_bw, rel=1e-12)
    # per-layer streams hide even at ONE microbatch (unlike ZeRO-1's
    # cross-microbatch window) — the scan itself is the window
    st1 = CM.predict_step_time(LAYERS, TOKENS, D, gradsync=gs,
                               microbatches=1)
    assert st1.hidden_comm > 0.0
    # blocking collectives never hide
    nr = GradSyncConfig(zero3=True, ring=False)
    stb = CM.predict_step_time(LAYERS, TOKENS, D, hw0, gradsync=nr,
                               microbatches=2)
    assert stb.hidden_comm == 0.0
    assert stb.exposed_comm == pytest.approx(
        vol * hw0.bytes_per_elem / hw0.link_bw, rel=1e-12)


@pytest.mark.parametrize("gs", [
    GradSyncConfig(zero=True),
    GradSyncConfig(zero=True, stream=False),
    GradSyncConfig(bucketed=True),
    GradSyncConfig(zero3=True),
    GradSyncConfig(zero3=True, prefetch=True),
], ids=["zero", "zero_nostream", "bucketed", "zero3", "zero3_prefetch"])
def test_cross_step_reduces_to_pr3_model_when_off(gs):
    """cross_step=False must be EXACTLY the prior exposed model (same
    total, same hideable); cross_step=True moves the terminal passes
    (param gather + last RS) into the hideable bucket without changing
    the total."""
    hw = CM.TPU_V5E
    import dataclasses as dc
    on = dc.replace(gs, cross_step=True)
    for mb in (1, 3):
        t_off, h_off = CM.dp_sync_time(4, 1e6, gs, mb, hw)
        t_on, h_on = CM.dp_sync_time(4, 1e6, on, mb, hw)
        assert t_on == t_off                 # hiding never changes total
        assert h_on > h_off                  # the window opens
        if gs.zero3:
            assert h_on == pytest.approx(t_on)   # everything hideable
        else:
            # exactly the two terminal passes move
            t_pass = t_off / (
                (mb if gs.stream else 1) + 1)
            assert h_on - h_off == pytest.approx(2 * t_pass)


def test_cross_step_off_is_default_and_degenerate():
    # the α=0/no-window degeneracy of PR 3 is untouched by the new knob
    hw = CM.HardwareParams(alpha=0.0)
    gs = GradSyncConfig(zero=True)
    st = CM.predict_step_time(LAYERS, TOKENS, D, hw, gradsync=gs,
                              microbatches=1)
    vol = CM.model_volume(LAYERS, TOKENS, D, gradsync=gs, microbatches=1)
    assert st.hidden_comm == 0.0
    assert st.exposed_comm == pytest.approx(
        vol * hw.bytes_per_elem / hw.link_bw, rel=1e-12)
    # cross_step widens the window under the SAME total
    on = GradSyncConfig(zero=True, cross_step=True)
    st_on = CM.predict_step_time(LAYERS, TOKENS, D, hw, gradsync=on,
                                 microbatches=1)
    assert st_on.hidden_comm > 0.0
    assert st_on.exposed_comm + st_on.hidden_comm == pytest.approx(
        st.exposed_comm, rel=1e-12)


def test_roofline_cross_step_split():
    by_kind = {"collective-permute": 1e9, "all-gather": 2e9,
               "all-reduce": 4e9}
    flops = 1e15  # large compute window: everything hideable fits
    off = RL.step_time_estimate(flops, by_kind)
    on = RL.step_time_estimate(flops, by_kind, cross_step=True)
    assert on.total <= off.total
    assert on.hidden_comm > off.hidden_comm
    # all-reduces stay exposed either way
    hw = CM.TPU_V5E
    assert on.exposed_comm >= 4e9 / hw.link_bw * 0.999
