"""ZeRO-sharded data-parallel gradient sync (core/gradsync.py).

The bucketed ring schedule must be a pure *decomposition* of the
blocking one: bucketed ring reduce-scatter + ZeRO-1 sharded AdamW +
param all-gather matches the blocking ``psum`` + replicated-AdamW
baseline — bitwise on exactly-summable values (the repo's standard for
ring-vs-blocking claims), within fp32 reassociation on a real model —
and the compiled DP path must contain collective-permute chains with NO
data-axis all-reduce left above scalar size. The α-β time model's DP
term must degenerate to the volume model at α = 0 with no overlap
window. Shapes scale down automatically on 4-device CI hosts.
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
from repro.core.overdecompose import split_batch
from repro.core.partition import ParamSpec, spec_tree_to_pspecs, \
    z_reduce_grads
from repro.launch import mesh as LM
from repro.launch import roofline as RL
from repro.launch import steps as ST
from repro.optim import adamw as OPT

# the acceptance mesh: 2 (data) x 2 (tensor); fits 4-device CI hosts
SHAPE_2X2 = (2, 2, 1, 1)
# mixed y/z mesh for the reduction-class coverage
SHAPE_YZ = (2, 1, 2, 2) if N_DEVICES >= 8 else (2, 1, 1, 2)
# dp=4 mesh whose data replica-group size is unambiguous in HLO
SHAPE_DP4 = (4, 1, 2, 1) if N_DEVICES >= 8 else (4, 1, 1, 1)


def _exact_random(key, shape):
    """Random fp32 small-int values: every reduction order is exact."""
    return jax.random.randint(key, shape, -4, 5).astype(jnp.float32)


# --------------------------------------------------------------------- #
# synthetic param/spec trees (optimizer-level tests)
# --------------------------------------------------------------------- #

def _toy_tree(with_yz: bool = False):
    """(global structs, ParamSpec tree) with mixed sharding/decay/class."""
    def leaf(shape, spec, z_reduced=False, y_reduce=False):
        return (jax.ShapeDtypeStruct(shape, jnp.float32),
                ParamSpec(spec, z_reduced, y_reduce))
    tree = {
        "blk": {
            "w_in": leaf((16, 8), P("x", None)),
            "w_out": leaf((8, 16), P(None, "x")),
            "norm_scale": leaf((16,), P()),          # no decay, replicated
            "bias": leaf((24,), P()),                # no decay
        },
        "emb": leaf((32, 4), P(None, None)),
    }
    if with_yz:
        tree["blk"]["w_z"] = leaf((8, 8), P("y", "z"), z_reduced=True)
        tree["blk"]["w_kv"] = leaf((4, 8), P(None, "y"), y_reduce=True)
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


# --------------------------------------------------------------------- #
# plan packing
# --------------------------------------------------------------------- #

def test_plan_packing_and_coverage():
    mesh = LM.make_smoke_mesh(SHAPE_YZ)
    axes = LM.bind_4d(mesh)
    structs, specs = _toy_tree(with_yz=True)
    cap_bytes = 256  # 64 fp32 elements: forces multiple buckets
    plan = GS.make_plan(structs, specs, axes, cap_bytes,
                        no_decay=OPT._no_decay)
    dp = axes.dp
    assert plan.dp == dp
    seen = {}
    for b in plan.buckets:
        assert b.padded % dp == 0 and b.padded >= b.size
        assert len(b.gid) == b.padded
        # greedy cap: only single-leaf buckets may exceed it
        if len(b.segments) > 1:
            assert b.size <= cap_bytes // 4
        off = 0
        for s in b.segments:
            assert s.offset == off  # contiguous layout
            off += s.size
            assert s.leaf not in seen
            seen[s.leaf] = b
        assert off == b.size
    assert len(seen) == plan.n_leaves  # every leaf exactly once
    # class purity: y/z flags match the leaf's ParamSpec
    spec_leaves = jax.tree.leaves(
        specs, is_leaf=lambda s: isinstance(s, ParamSpec))
    for i, ps in enumerate(spec_leaves):
        b = seen[i]
        assert b.z_reduced == ps.z_reduced and b.y_reduce == ps.y_reduce
    # padding slack is bounded by one ring block per bucket
    assert plan.padded_elements - plan.total_elements \
        < len(plan.buckets) * dp
    assert plan.shard_sizes == tuple(b.padded // dp for b in plan.buckets)


def test_plan_decay_and_norm_groups():
    mesh = LM.make_smoke_mesh(SHAPE_2X2)
    axes = LM.bind_4d(mesh)
    structs, specs = _toy_tree()
    plan = GS.make_plan(structs, specs, axes, 1 << 20,
                        no_decay=OPT._no_decay)
    flat, _ = jax.tree_util.tree_flatten_with_path(structs)
    spec_leaves = jax.tree.leaves(
        specs, is_leaf=lambda s: isinstance(s, ParamSpec))
    by_leaf = {s.leaf: (b, s) for b in plan.buckets for s in b.segments}
    for i, ((path, _), ps) in enumerate(zip(flat, spec_leaves)):
        b, seg = by_leaf[i]
        gids = set(b.gid[seg.offset:seg.offset + seg.size].tolist())
        assert len(gids) == 1  # one group per leaf
        meta = b.groups[gids.pop()]
        assert meta.decay == (not OPT._no_decay(path))
        names = tuple(n for e in ps.spec if e is not None
                      for n in (e if isinstance(e, tuple) else (e,)))
        assert meta.norm_names == names


def test_flatten_unflatten_roundtrip():
    mesh = LM.make_smoke_mesh(SHAPE_2X2)
    axes = LM.bind_4d(mesh)
    structs, specs = _toy_tree()
    plan = GS.make_plan(structs, specs, axes, 512)
    # local-shaped leaves (shapes from the plan's own segments)
    leaves = [None] * plan.n_leaves
    rng = np.random.RandomState(0)
    for b in plan.buckets:
        for s in b.segments:
            leaves[s.leaf] = jnp.asarray(
                rng.randint(-4, 5, s.shape).astype(np.float32))
    for b in plan.buckets:
        flat = GS.flatten_bucket(leaves, b)
        assert flat.shape == (b.padded,) and flat.dtype == jnp.float32
        for i, arr in GS.unflatten_bucket(flat, b):
            np.testing.assert_array_equal(np.asarray(arr),
                                          np.asarray(leaves[i]))


def test_gradsync_config_validation():
    with pytest.raises(ValueError):
        GradSyncConfig(bucket_mb=0.0)
    assert not GradSyncConfig().enabled
    assert GradSyncConfig(bucketed=True).enabled
    assert GradSyncConfig(zero=True).enabled


# --------------------------------------------------------------------- #
# bucketed sync == blocking psum (bitwise, exact values)
# --------------------------------------------------------------------- #

@pytest.mark.parametrize("ring", [True, False], ids=["ring", "blocking"])
def test_bucketed_sync_matches_psum(ring):
    mesh = LM.make_smoke_mesh(SHAPE_YZ)
    axes = LM.bind_4d(mesh)
    structs, specs = _toy_tree(with_yz=True)
    pspecs = spec_tree_to_pspecs(specs)
    plan = GS.make_plan(structs, specs, axes, 256,
                        no_decay=OPT._no_decay)

    def local_grads(gbase):
        # per-rank partials: data ranks always differ; z/y ranks differ
        # only where the baseline schedule reduces over z/y
        didx = M.axis_index(axes.data).astype(jnp.float32)
        zidx = M.axis_index(axes.z).astype(jnp.float32)
        yidx = M.axis_index(axes.y).astype(jnp.float32)

        def one(g, s):
            f = 1.0 + didx
            if not s.z_reduced:
                f = f + 2.0 * zidx
            if s.y_reduce:
                f = f + 3.0 * yidx
            return g * f
        return jax.tree.map(one, gbase, specs,
                            is_leaf=lambda s: isinstance(s, ParamSpec))

    def baseline(gbase):
        grads = local_grads(gbase)
        grads = jax.tree.map(lambda g: M.psum(g, axes.data), grads)
        return z_reduce_grads(grads, specs, axes, M.psum)

    def bucketed(gbase):
        grads = local_grads(gbase)
        shards = GS.reduce_scatter_grads(grads, plan, axes, ring=ring)
        shards = GS.tensor_reduce_shards(shards, plan, axes)
        return GS.all_gather_grads(shards, plan, axes, ring=ring)

    gbase = _toy_values(structs)
    out_b = jax.jit(shard_map(baseline, mesh=mesh, in_specs=(pspecs,),
                              out_specs=pspecs, check_vma=False))(gbase)
    out_r = jax.jit(shard_map(bucketed, mesh=mesh, in_specs=(pspecs,),
                              out_specs=pspecs, check_vma=False))(gbase)
    for a, b in zip(jax.tree.leaves(out_b), jax.tree.leaves(out_r)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# --------------------------------------------------------------------- #
# ZeRO-1 update == blocking psum + replicated AdamW (bitwise, 2x2 mesh)
# --------------------------------------------------------------------- #

def test_zero_update_bitwise_vs_baseline():
    mesh = LM.make_smoke_mesh(SHAPE_2X2)
    axes = LM.bind_4d(mesh)
    structs, specs = _toy_tree()
    pspecs = spec_tree_to_pspecs(specs)
    plan = GS.make_plan(structs, specs, axes, 256,
                        no_decay=OPT._no_decay)
    cfg = OPT.AdamWConfig(lr=1e-2, warmup_steps=1, total_steps=10)
    sspecs = OPT.state_pspecs(pspecs)
    opt_out = jax.tree.map(lambda s: {"m": s, "v": s, "master": s},
                           pspecs,
                           is_leaf=lambda x: isinstance(x, P))

    def grads_of(params, gbase):
        didx = M.axis_index(axes.data).astype(jnp.float32)
        return jax.tree.map(lambda g: g * (1.0 + didx), gbase)

    # both schedules inside ONE program (the repo's standard for bitwise
    # ring-vs-blocking claims: separate jit compilations may fuse FMAs
    # differently, which is a compiler artifact, not a schedule one)
    def both(params, gbase):
        p, s = params, OPT.init_state(params)
        for _ in range(2):  # two steps: step-count/bias-corr coverage
            grads = jax.tree.map(lambda g: M.psum(g, axes.data),
                                 grads_of(p, gbase))
            grads = z_reduce_grads(grads, specs, axes, M.psum)
            p, s, m = OPT.apply_updates(p, grads, s, specs, axes, cfg)
        base = (p, m["grad_norm"], s["opt"])
        p, s = params, GS.init_sharded_state(params, plan, axes)
        for _ in range(2):
            shards = GS.reduce_scatter_grads(grads_of(p, gbase), plan,
                                             axes, ring=True)
            shards = GS.tensor_reduce_shards(shards, plan, axes)
            p, s, m = OPT.apply_updates_sharded(shards, s, plan, axes,
                                                cfg, ring=True)
        zero = (p, m["grad_norm"],
                GS.gather_sharded_state(s, plan, axes)["opt"])
        return base + zero

    params = _toy_values(structs, seed=1)
    gbase = _toy_values(structs, seed=2)
    out_specs = (pspecs, P(), opt_out)
    pb, nb, sb, pz, nz, sz = jax.jit(shard_map(
        both, mesh=mesh, in_specs=(pspecs, pspecs),
        out_specs=out_specs + out_specs, check_vma=False))(params, gbase)
    assert float(nb) == float(nz), "grad norm must match bitwise"
    for a, b in zip(jax.tree.leaves(pb), jax.tree.leaves(pz)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(sb), jax.tree.leaves(sz)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# --------------------------------------------------------------------- #
# full train step: parity, HLO shape, memory
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
    if gs.zero:
        tools = ST.make_gradsync_tools(cfg, mesh, axes, opts)
        state = tools.init(params)
    else:
        tools, state = None, init_state(params)
    rng = np.random.RandomState(0)
    batch = {"tokens": jnp.asarray(rng.randint(0, cfg.vocab_size, (8, 32)),
                                   jnp.int32),
             "labels": jnp.asarray(rng.randint(0, cfg.vocab_size, (8, 32)),
                                   jnp.int32)}
    return cfg, mesh, axes, opts, fn, params, state, batch, tools


ZERO_MODES = [
    ("bucketed", GradSyncConfig(bucketed=True, bucket_mb=0.25)),
    ("zero", GradSyncConfig(zero=True, bucket_mb=0.25)),
    ("zero_noring", GradSyncConfig(zero=True, bucket_mb=0.25, ring=False)),
    ("zero_nostream", GradSyncConfig(zero=True, bucket_mb=0.25,
                                     stream=False)),
]


def test_train_step_parity_all_modes():
    results = {}
    for name, gs in [("base", GradSyncConfig())] + ZERO_MODES:
        _, _, _, _, fn, params, state, batch, _ = _model_setup(
            SHAPE_2X2, gs)
        p, s = params, state
        for _ in range(3):
            p, s, m = fn(p, s, batch)
        results[name] = (float(m["loss"]), float(m["grad_norm"]),
                         [np.asarray(x) for x in jax.tree.leaves(p)])
    lb, nb, pb = results["base"]
    for name, _ in ZERO_MODES:
        l, n, pz = results[name]
        assert abs(l - lb) < 1e-5, (name, l, lb)
        assert abs(n - nb) < 1e-4 * max(1.0, nb), (name, n, nb)
        gap = max(float(np.max(np.abs(a - b))) for a, b in zip(pb, pz))
        assert gap < 5e-6, f"{name}: params diverged from baseline: {gap}"


def test_zero_hlo_collective_permute_no_data_allreduce():
    dp = SHAPE_DP4[0]
    hlos = {}
    for name, gs in [("base", GradSyncConfig()),
                     ("zero", GradSyncConfig(zero=True, bucket_mb=0.25))]:
        _, _, _, _, fn, params, state, batch, _ = _model_setup(
            SHAPE_DP4, gs)
        hlos[name] = fn.lower(params, state, batch).compile().as_text()
    ops = {k: RL.parse_collective_ops(h) for k, h in hlos.items()}

    def big_dp_ar(k):
        return sum(1 for op in ops[k] if op.kind == "all-reduce"
                   and op.group_size == dp and op.raw_bytes > 2048)

    def permutes(k):
        return sum(1 for op in ops[k] if op.kind == "collective-permute")

    assert big_dp_ar("base") > 0          # the blocking path psums per leaf
    assert big_dp_ar("zero") == 0, \
        "DP gradient all-reduces survived the ZeRO ring schedule"
    assert permutes("zero") > permutes("base"), \
        "DP rings must lower to collective-permute chains"


def test_zero_state_memory_sharded_by_dp():
    gs = GradSyncConfig(zero=True, bucket_mb=0.25)
    cfg, mesh, axes, opts, _, params, state, _, tools = _model_setup(
        SHAPE_DP4, gs)
    plan = tools.plan
    per_rank = sum(plan.shard_sizes)  # fp32 elements per m/v/master each
    # each rank holds ~1/dp of the fp32 state (+ bounded padding slack)
    assert per_rank * plan.dp <= plan.total_elements \
        + len(plan.buckets) * plan.dp
    # plan covers every param element exactly once, at its local size
    structs, mspecs = ST.init_model(cfg, axes.with_overlap(opts.overlap),
                                    abstract=True, dtype=opts.dtype)
    spec_leaves = jax.tree.leaves(
        mspecs, is_leaf=lambda s: isinstance(s, ParamSpec))
    expect = sum(
        int(np.prod(GS._local_shape(tuple(l.shape), tuple(s.spec), axes))
            or 1)
        for l, s in zip(jax.tree.leaves(structs), spec_leaves))
    assert plan.total_elements == expect
    # abstract state (dry-run) matches the real init's global shapes
    astate = ST.abstract_opt_state(cfg, axes, opts)
    real = jax.tree.map(lambda x: (x.shape, str(x.dtype)), state)
    abst = jax.tree.map(lambda x: (x.shape, str(x.dtype)), astate)
    assert real == abst


# --------------------------------------------------------------------- #
# checkpoint round-trip across different g_data
# --------------------------------------------------------------------- #

def _toy_tools(mesh, axes, structs, specs, plan):
    """shard_map'd init/gather/scatter for the synthetic tree (what
    launch.steps.make_gradsync_tools builds for a real model)."""
    pspecs = spec_tree_to_pspecs(specs)
    sspecs = GS.sharded_state_pspecs(plan, axes)
    fullspecs = OPT.state_pspecs(pspecs)
    init = jax.jit(shard_map(
        lambda p: GS.init_sharded_state(p, plan, axes), mesh=mesh,
        in_specs=(pspecs,), out_specs=sspecs, check_vma=False))
    gather = jax.jit(shard_map(
        lambda s: GS.gather_sharded_state(s, plan, axes), mesh=mesh,
        in_specs=(sspecs,), out_specs=fullspecs, check_vma=False))
    scatter = jax.jit(shard_map(
        lambda s: GS.scatter_full_state(s, plan, axes), mesh=mesh,
        in_specs=(fullspecs,), out_specs=sspecs, check_vma=False))
    return init, gather, scatter, pspecs, sspecs, fullspecs


def test_checkpoint_roundtrip_across_gdata(tmp_path):
    """Save ZeRO state under g_data=2, restore under g_data=4, and
    bitwise-compare the resumed step against staying on the source mesh
    (exact-valued grads; per-rank partials scale 1/dp so the *global*
    gradient is mesh-independent)."""
    from repro.checkpoint import ckpt

    structs, specs = _toy_tree()
    cfg = OPT.AdamWConfig(lr=1e-2, warmup_steps=1, total_steps=10)
    path = os.path.join(tmp_path, "zero.npz")
    meshes = {"A": LM.make_smoke_mesh(SHAPE_2X2),
              "B": LM.make_smoke_mesh((4, 1, 1, 1))}
    env = {}
    for k, mesh in meshes.items():
        axes = LM.bind_4d(mesh)
        plan = GS.make_plan(structs, specs, axes, 256,
                            no_decay=OPT._no_decay)
        env[k] = (mesh, axes, plan) + _toy_tools(mesh, axes, structs,
                                                 specs, plan)

    def step_fn(mesh, axes, plan, pspecs, sspecs):
        def body(params, state, gbase):
            dp = float(axes.dp)
            grads = jax.tree.map(lambda g: g * (1.0 / dp), gbase)
            shards = GS.reduce_scatter_grads(grads, plan, axes)
            shards = GS.tensor_reduce_shards(shards, plan, axes)
            return OPT.apply_updates_sharded(shards, state, plan, axes,
                                             cfg)[:2]
        return jax.jit(shard_map(body, mesh=mesh,
                                 in_specs=(pspecs, sspecs, pspecs),
                                 out_specs=(pspecs, sspecs),
                                 check_vma=False))

    params = _toy_values(structs, seed=1)
    gbase = _toy_values(structs, seed=2)

    # source mesh A: init, one step, save
    mesh, axes, plan, init, gather, scatter, pspecs, sspecs, fullspecs = \
        env["A"]
    step_a = step_fn(mesh, axes, plan, pspecs, sspecs)
    pa, sa = step_a(params, init(params), gbase)
    ckpt.save_sharded(path, jax.tree.map(np.asarray, pa), sa, gather,
                      step=1, extra={"dp_bucket_mb": 0.25 / 1024})
    # continue on A: the reference trajectory
    pa2, sa2 = step_a(pa, sa, gbase)
    ref_full = jax.device_get(gather(sa2))

    # restore on mesh B (different g_data), resume one step
    mesh, axes, plan, init, gather, scatter, pspecs, sspecs, fullspecs = \
        env["B"]
    like_state = {"opt": jax.tree.map(
        lambda s: {"m": jax.ShapeDtypeStruct(s.shape, jnp.float32),
                   "v": jax.ShapeDtypeStruct(s.shape, jnp.float32),
                   "master": jax.ShapeDtypeStruct(s.shape, jnp.float32)},
        structs), "step": jax.ShapeDtypeStruct((), jnp.int32)}
    pb, sb, step = ckpt.restore_sharded(path, structs, like_state, scatter)
    assert step == 1
    # round trip is lossless: gather(scatter(full)) == full
    rt_full = jax.device_get(gather(sb))
    saved_full, _ = ckpt.restore(path, like_state, root="opt_state")
    for a, b in zip(jax.tree.leaves(rt_full), jax.tree.leaves(saved_full)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    pb2, sb2 = step_fn(mesh, axes, plan, pspecs, sspecs)(
        jax.tree.map(jnp.asarray, pb), sb, gbase)
    res_full = jax.device_get(gather(sb2))
    # the resumed step matches the uninterrupted run bitwise
    for a, b in zip(jax.tree.leaves(pa2), jax.tree.leaves(pb2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(ref_full), jax.tree.leaves(res_full)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# --------------------------------------------------------------------- #
# time/volume model: DP term + degeneracy + hiding
# --------------------------------------------------------------------- #

LAYERS = CM.transformer_layers(256, 2)
D = CM.Decomposition(4, 2, 2, 2)
TOKENS = 4096
GS_CFGS = [None,
           GradSyncConfig(bucketed=True),
           GradSyncConfig(zero=True),
           GradSyncConfig(zero=True, stream=False)]


def test_dp_sync_volume_formulas():
    buf = 120.0
    # blocking == bandwidth-optimal all-reduce
    assert CM.dp_sync_volume(4, buf) == CM.allreduce_volume(4, buf)
    # one microbatch: RS + AG == the all-reduce volume exactly
    gs = GradSyncConfig(zero=True)
    assert CM.dp_sync_volume(4, buf, gs, 1) == \
        pytest.approx(CM.allreduce_volume(4, buf))
    # streamed: one RS per microbatch + one AG
    assert CM.dp_sync_volume(4, buf, gs, 3) == \
        pytest.approx(4 * CM.gather_or_scatter_volume(4, buf))
    # stream off: volume is microbatch-independent
    ns = GradSyncConfig(zero=True, stream=False)
    assert CM.dp_sync_volume(4, buf, ns, 3) == \
        pytest.approx(CM.allreduce_volume(4, buf))
    assert CM.dp_sync_volume(1, buf, gs, 3) == 0.0


@pytest.mark.parametrize("gs", GS_CFGS, ids=lambda g: (
    "none" if g is None else
    f"{'zero' if g.zero else 'bucketed'}{'_nostream' if not g.stream else ''}"))
def test_dp_time_model_degenerates_to_volume(gs):
    """α=0 + no overlap window (one microbatch / stream off): exposed
    comm == model volume / bandwidth, exactly — the acceptance pin for
    the new bucketed DP path."""
    hw = CM.HardwareParams(alpha=0.0)
    for mb in ([1] if gs is None or gs.stream else [1, 4]):
        st = CM.predict_step_time(LAYERS, TOKENS, D, hw, gradsync=gs,
                                  microbatches=mb)
        vol = CM.model_volume(LAYERS, TOKENS, D, gradsync=gs,
                              microbatches=mb)
        assert st.hidden_comm == 0.0
        assert st.exposed_comm == pytest.approx(
            vol * hw.bytes_per_elem / hw.link_bw, rel=1e-12)


def test_dp_streaming_hides_under_microbatch_window():
    gs = GradSyncConfig(zero=True)
    st1 = CM.predict_step_time(LAYERS, TOKENS, D, gradsync=gs,
                               microbatches=1)
    st2 = CM.predict_step_time(LAYERS, TOKENS, D, gradsync=gs,
                               microbatches=2)
    assert st1.hidden_comm == 0.0      # nothing to ride under
    assert st2.hidden_comm > 0.0       # mb 0's RS hides under mb 1's bwd
    # conservation: hiding re-buckets time, it does not destroy it
    hw0 = CM.HardwareParams(overlap_efficiency=0.0)
    st2_exposed = CM.predict_step_time(LAYERS, TOKENS, D, hw0, gradsync=gs,
                                       microbatches=2)
    assert st2.exposed_comm + st2.hidden_comm == pytest.approx(
        st2_exposed.exposed_comm, rel=1e-12)
    # the blocking DP path never hides (it runs after the loop)
    stb = CM.predict_step_time(LAYERS, TOKENS, D, gradsync=None,
                               microbatches=2)
    assert stb.hidden_comm == 0.0


def test_dp_bucket_count_is_latency_knob():
    hw = CM.HardwareParams(alpha=1e-5)
    big = GradSyncConfig(zero=True, bucket_mb=64.0)
    small = GradSyncConfig(zero=True, bucket_mb=0.0625)
    t_big, _ = CM.dp_sync_time(4, 1e6, big, 1, hw)
    t_small, _ = CM.dp_sync_time(4, 1e6, small, 1, hw)
    assert t_small > t_big  # more rings, more α
    # α=0: bucket count is invisible (pure bandwidth)
    hw0 = CM.HardwareParams(alpha=0.0)
    assert CM.dp_sync_time(4, 1e6, big, 1, hw0)[0] == \
        pytest.approx(CM.dp_sync_time(4, 1e6, small, 1, hw0)[0])


# --------------------------------------------------------------------- #
# satellites: fp32 microbatch accumulation; split_batch errors
# --------------------------------------------------------------------- #

def test_overdecompose_fp32_accumulation_parity():
    """overdecompose=2 must track the single-batch (=1) trajectory to
    fp32-reassociation precision now that microbatch grads accumulate in
    fp32."""
    losses = {}
    for od in (1, 2):
        _, _, _, _, fn, params, state, batch, _ = _model_setup(
            SHAPE_2X2, GradSyncConfig(), overdecompose=od)
        p, s = params, state
        for _ in range(3):
            p, s, m = fn(p, s, batch)
        losses[od] = float(m["loss"])
    assert abs(losses[1] - losses[2]) < 1e-5, losses


def test_split_batch_error_is_clear():
    batch = {"tokens": jnp.zeros((3, 4), jnp.int32)}
    with pytest.raises(ValueError, match="per-shard batch 3.*not "
                                         "divisible by the "
                                         "overdecomposition factor"):
        split_batch(batch, 2)
    mesh = LM.make_smoke_mesh(SHAPE_2X2)
    axes = LM.bind_4d(mesh)
    with pytest.raises(ValueError, match="global batch must be divisible "
                                         "by batch_shards"):
        split_batch(batch, 2, axes=axes)
    with pytest.raises(ValueError, match="scalar"):
        split_batch({"pos": jnp.zeros((), jnp.int32)}, 2)
