"""Ring-decomposed collective matmuls (core/collective_matmul.py) and the
α-β overlap-aware time model (core/comm_model.py).

The overlapped schedules must be pure *decompositions* of the blocking
ones: same forward outputs and same dX/dW gradients (within fp32-accum
reassociation) across (x, y, z) decompositions of the CPU smoke mesh,
with collective-permute chains in the HLO where the monolithic weight
all-gather / reduce-scatter — and, with ``all_reduce`` on, the x/y
activation all-reduces — used to be. Shapes scale down automatically on
4-device CI hosts (conftest.N_DEVICES).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from conftest import N_DEVICES, fitting_shapes
from test_layer_scopes import _program
from repro.core import collective_matmul as CMM
from repro.core import comm_model as CM
from repro.core import mesh as M
from repro.core import parallel as PP
from repro.core.overlap import OverlapConfig
from repro.launch import mesh as LM
from repro.launch import roofline as RL

K, N, B, S = 16, 24, 8, 8

SHAPES_4D = fitting_shapes([(1, 2, 2, 2), (2, 2, 1, 2), (2, 1, 2, 2),
                            (1, 1, 2, 4), (2, 2, 2, 1),
                            (1, 2, 2, 1), (1, 1, 2, 2)])
# the deepest-z shape the host holds (z rings of size > 2)
SHAPE_Z = (1, 2, 2, 2) if N_DEVICES >= 8 else (1, 1, 2, 2)
OVERLAPS = [OverlapConfig.all_on(),
            OverlapConfig.all_on(z_chunks=2),
            OverlapConfig.all_on(ar_chunks=2),
            OverlapConfig(all_reduce=True),
            OverlapConfig.all_on(cache_weight_gather=True)]


def _ids(v):
    if isinstance(v, OverlapConfig):
        tags = []
        if v.matmul:
            tags.append(f"z{v.z_chunks}")
        if v.all_reduce:
            tags.append(f"ar{v.ar_chunks}")
        if v.cache_weight_gather:
            tags.append("cache")
        return "_".join(tags)
    return str(v)


def _exact_random(key, shape):
    """Random fp32 values whose sums/products are exact (small ints), so
    every reduction order gives bitwise-identical results."""
    return jax.random.randint(key, shape, -4, 5).astype(jnp.float32)


# --------------------------------------------------------------------- #
# ring primitives == blocking collectives
# --------------------------------------------------------------------- #

@pytest.mark.parametrize("shape", SHAPES_4D, ids=str)
def test_ring_primitives_match_blocking(shape):
    mesh = LM.make_smoke_mesh(shape)
    axes = LM.bind_4d(mesh)

    def body(v):
        ag = M.all_gather(v, axes.z, dim=1)
        rag = M.ring_all_gather(v, axes.z, dim=1)
        rs = M.psum_scatter(ag, axes.z, dim=1)
        rrs = M.ring_reduce_scatter(ag, axes.z, dim=1)
        d_ag = jnp.max(jnp.abs(ag - rag))
        d_rs = jnp.max(jnp.abs(rs - rrs))
        return M.pmax(M.pmax(jnp.stack([d_ag, d_rs]), axes.z), axes.data)

    f = shard_map(body, mesh=mesh,
                  in_specs=axes.pspec(axes.x, axes.y),
                  out_specs=P(), check_vma=False)
    v = jax.random.normal(jax.random.PRNGKey(0),
                          (8 * shape[1], 16 * shape[2]))
    d_ag, d_rs = np.asarray(jax.jit(f)(v))
    assert d_ag == 0.0, "ring_all_gather must be bitwise all_gather"
    assert d_rs < 1e-5, d_rs


def test_ring_identity_on_unmapped_axis():
    shape = (2, 2, 2, 1) if N_DEVICES >= 8 else (1, 2, 2, 1)
    mesh = LM.make_smoke_mesh(shape)
    axes = M.bind_axes(mesh, data=("data",), x="x", y="y")  # z unmapped

    def body(v):
        a = M.ring_all_gather(v, axes.z, dim=1)
        b = M.ring_reduce_scatter(v, axes.z, dim=1)
        c = M.ppermute_ring(v, axes.z)
        d = M.ring_all_reduce(v, axes.z)
        return jnp.max(jnp.abs(a - v) + jnp.abs(b - v) + jnp.abs(c - v)
                       + jnp.abs(d - v))

    f = shard_map(body, mesh=mesh, in_specs=P(None, None),
                  out_specs=P(), check_vma=False)
    assert float(jax.jit(f)(jnp.ones((4, 4)))) == 0.0


def test_ppermute_ring_shifts():
    shape = (1, 1, 2, 4) if N_DEVICES >= 8 else (1, 1, 1, 4)
    mesh = LM.make_smoke_mesh(shape)
    axes = LM.bind_4d(mesh)

    def body(v):
        # rank i receives rank i-1's value -> the global view rotates
        return M.ppermute_ring(v, axes.z)

    f = shard_map(body, mesh=mesh, in_specs=P("z"), out_specs=P("z"),
                  check_vma=False)
    out = np.asarray(jax.jit(f)(jnp.arange(4.0)))
    np.testing.assert_array_equal(out, np.asarray([3.0, 0.0, 1.0, 2.0]))


# --------------------------------------------------------------------- #
# ring_all_reduce == psum (satellite: identity / tuple axes / bitwise)
# --------------------------------------------------------------------- #

@pytest.mark.parametrize("logical", ["x", "y", "z", "data"])
@pytest.mark.parametrize("shape", SHAPES_4D, ids=str)
def test_ring_all_reduce_matches_psum(shape, logical):
    """ring_all_reduce == psum over every mesh axis: bitwise on
    exactly-summable values (any ring size — the decomposition must move
    the right blocks to the right places), and within reassociation
    tolerance on generic floats."""
    mesh = LM.make_smoke_mesh(shape)
    axes = LM.bind_4d(mesh)
    ax = axes.axis(logical)

    def body(v):
        d = jnp.max(jnp.abs(M.ring_all_reduce(v, ax, dim=-1)
                            - M.psum(v, ax)))
        return M.pmax(M.pmax(M.pmax(M.pmax(
            d, axes.data), axes.x), axes.y), axes.z)

    f = jax.jit(shard_map(body, mesh=mesh, in_specs=P(), out_specs=P(),
                          check_vma=False))
    exact = _exact_random(jax.random.PRNGKey(0), (4, 8))
    assert float(f(exact)) == 0.0, "schedule must be bitwise on exact sums"
    fuzzy = jax.random.normal(jax.random.PRNGKey(1), (4, 8))
    assert float(f(fuzzy)) < 1e-5


def test_ring_all_reduce_tuple_axis():
    """A tuple (multi-name) ring axis must flatten into ONE ring, not
    fall back to blocking: correct sum AND no all-reduce in the HLO."""
    shape = (1, 2, 2, 2) if N_DEVICES >= 8 else (1, 2, 2, 1)
    mesh = LM.make_smoke_mesh(shape)
    names = ("x", "y", "z") if N_DEVICES >= 8 else ("x", "y")

    def body(v):
        return M.ring_all_reduce(v, names, dim=-1)

    f = jax.jit(shard_map(body, mesh=mesh, in_specs=P(), out_specs=P(),
                          check_vma=False))
    v = _exact_random(jax.random.PRNGKey(0), (2, 8))
    p = int(np.prod(shape[1:]))
    np.testing.assert_array_equal(np.asarray(f(v)), np.asarray(v) * p)
    stats = RL.parse_collectives(f.lower(v).compile().as_text())
    assert stats.counts.get("all-reduce", 0) == 0
    assert stats.counts.get("collective-permute", 0) >= 1


def test_ring_all_reduce_fallback_nondivisible():
    """Rings (p > 2) that don't split the dim evenly must silently fall
    back to the blocking psum — correctness over decomposition."""
    shape = (1, 1, 2, 4) if N_DEVICES >= 8 else (1, 1, 1, 4)
    mesh = LM.make_smoke_mesh(shape)
    axes = LM.bind_4d(mesh)

    def body(v):
        return M.ring_all_reduce(v, axes.z, dim=-1)  # 6 % 4 != 0

    f = jax.jit(shard_map(body, mesh=mesh, in_specs=P(), out_specs=P(),
                          check_vma=False))
    v = _exact_random(jax.random.PRNGKey(0), (2, 6))
    np.testing.assert_array_equal(np.asarray(f(v)), np.asarray(v) * 4)


@pytest.mark.parametrize("chunks", [1, 2, 3])
def test_ar_matmul_bitwise_vs_psum(chunks):
    """Satellite acceptance: the fused AR-matmul forward is bitwise
    identical to the blocking GEMM + psum at matching chunk counts (on
    exactly-summable values, where reduction order cannot hide schedule
    bugs)."""
    shape = (1, 2, 2, 2) if N_DEVICES >= 8 else (1, 2, 2, 1)
    mesh = LM.make_smoke_mesh(shape)
    x = _exact_random(jax.random.PRNGKey(0), (B, K))
    w = _exact_random(jax.random.PRNGKey(1), (K, N))

    def body(x, w):
        blocking = jax.lax.psum(
            jax.lax.dot_general(x, w, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32
                                ).astype(x.dtype), ("x", "y"))
        ring2 = CMM.ar_matmul(x, w, "x", chunks=chunks)       # p = 2 path
        ring2 = jax.lax.psum(ring2, "y")
        ring4 = CMM.ar_matmul(x, w, ("x", "y"), chunks=chunks)  # tuple ring
        d2 = jnp.max(jnp.abs(blocking - ring2))
        d4 = jnp.max(jnp.abs(blocking - ring4))
        return jax.lax.pmax(jax.lax.pmax(jnp.stack([d2, d4]), "x"), "y")

    f = jax.jit(shard_map(body, mesh=mesh, in_specs=(P(), P()),
                          out_specs=P(), check_vma=False))
    d2, d4 = np.asarray(f(x, w))
    assert d2 == 0.0 and d4 == 0.0, (d2, d4)


# --------------------------------------------------------------------- #
# overlapped tp primitives == blocking (values AND gradients)
# --------------------------------------------------------------------- #

def _run_matmul(mesh, base, axes, x, w, in_shard, out_shard):
    wspec = PP.wspec(base, in_shard, out_shard)
    in_ax = base.axis(in_shard) if in_shard else None
    out_ax = base.axis(out_shard) if out_shard else None
    xspec = base.pspec(base.batch_axes(), None, in_ax)

    def loss(x, w):
        y = PP.tp_matmul(x, w, axes, in_shard, out_shard)
        s = jnp.sum(y.astype(jnp.float32) ** 2)
        return PP.ar_bwd_identity(
            s, M._names(axes.batch_axes()) + M._names(out_ax))

    def step(x, w):
        v, (gx, gw) = jax.value_and_grad(loss, argnums=(0, 1))(x, w)
        return v, gx, M.psum(gw, axes.data)

    f = shard_map(step, mesh=mesh, in_specs=(xspec, wspec),
                  out_specs=(P(), xspec, wspec), check_vma=False)
    return jax.jit(f)(x, w)


@pytest.mark.parametrize("shards", [("x", "y"), ("y", "x")],
                         ids=["normal", "transposed"])
@pytest.mark.parametrize("shape", SHAPES_4D, ids=str)
@pytest.mark.parametrize("ov", OVERLAPS, ids=_ids)
def test_tp_matmul_overlap_matches_blocking(shape, ov, shards):
    """Fwd + dX + dW parity, normal and transposed (§4.1) layers."""
    mesh = LM.make_smoke_mesh(shape)
    base = LM.bind_4d(mesh)
    x = jax.random.normal(jax.random.PRNGKey(0), (B, S, K))
    w = jax.random.normal(jax.random.PRNGKey(1), (K, N)) * 0.1
    in_shard, out_shard = shards
    vb, gxb, gwb = _run_matmul(mesh, base, base, x, w, in_shard, out_shard)
    vo, gxo, gwo = _run_matmul(mesh, base, base.with_overlap(ov), x, w,
                               in_shard, out_shard)
    np.testing.assert_allclose(np.asarray(vb), np.asarray(vo), rtol=2e-5)
    np.testing.assert_allclose(np.asarray(gxb), np.asarray(gxo),
                               rtol=2e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(gwb), np.asarray(gwo),
                               rtol=2e-5, atol=1e-5)


def test_tp_matmul_tuple_z_ring():
    """Tuple (multi-name) z axes must take the fused ring path — parity
    with blocking AND collective-permutes (not a blocking fallback) in
    the HLO."""
    shape = (1, 2, 2, 2) if N_DEVICES >= 8 else (1, 1, 2, 2)
    mesh = LM.make_smoke_mesh(shape)
    # depth axis spans two mesh names: gz = 4
    base = M.bind_axes(mesh, data=("data",), x="x", z=("y", "z"))
    x = jax.random.normal(jax.random.PRNGKey(0), (B, S, K))
    w = jax.random.normal(jax.random.PRNGKey(1), (K, N)) * 0.1
    vb, gxb, gwb = _run_matmul(mesh, base, base, x, w, "x", None)
    ov = OverlapConfig.all_on()
    axes = base.with_overlap(ov)
    vo, gxo, gwo = _run_matmul(mesh, base, axes, x, w, "x", None)
    np.testing.assert_allclose(np.asarray(vb), np.asarray(vo), rtol=2e-5)
    np.testing.assert_allclose(np.asarray(gxb), np.asarray(gxo),
                               rtol=2e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(gwb), np.asarray(gwo),
                               rtol=2e-5, atol=1e-5)

    wspec = PP.wspec(base, "x", None)
    xspec = base.pspec(base.batch_axes(), None, base.x)

    def fwd(x, w):
        return PP.tp_matmul(x, w, axes, "x", None)

    f = shard_map(fwd, mesh=mesh, in_specs=(xspec, wspec),
                  out_specs=base.pspec(base.batch_axes(), None, None),
                  check_vma=False)
    stats = RL.parse_collectives(jax.jit(f).lower(x, w).compile().as_text())
    assert stats.counts.get("all-gather", 0) == 0, stats.counts
    assert stats.counts.get("collective-permute", 0) >= 1, stats.counts


@pytest.mark.parametrize("ov", OVERLAPS, ids=_ids)
def test_batched_matmul_overlap_matches_blocking(ov):
    mesh = LM.make_smoke_mesh(SHAPE_Z)
    base = LM.bind_4d(mesh)
    E, C = 4, 8
    x = jax.random.normal(jax.random.PRNGKey(0), (E, C, K))
    w = jax.random.normal(jax.random.PRNGKey(1), (E, K, N)) * 0.1
    xspec, wspec = P("y", None, "x"), P("y", "x", "z")

    def run(axes):
        def loss(x, w):
            y = PP.tp_batched_matmul(x, w, axes, "x", None)
            return PP.ar_bwd_identity(
                jnp.sum(y.astype(jnp.float32) ** 2), ("y", "z"))

        def step(x, w):
            v, g = jax.value_and_grad(loss, argnums=(0, 1))(x, w)
            return v, g[0], g[1]

        f = shard_map(step, mesh=mesh, in_specs=(xspec, wspec),
                      out_specs=(P(), xspec, wspec), check_vma=False)
        return jax.jit(f)(x, w)

    rb = run(base)
    ro = run(base.with_overlap(ov))
    for name, a, b in zip(("val", "dx", "dw"), rb, ro):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=1e-5, err_msg=name)


@pytest.mark.parametrize("shape",
                         fitting_shapes([(1, 2, 2, 2), (1, 1, 2, 4),
                                         (1, 2, 2, 1), (1, 1, 2, 2)]),
                         ids=str)
@pytest.mark.parametrize("ov", OVERLAPS, ids=_ids)
def test_tied_logits_overlap_matches_blocking(shape, ov):
    mesh = LM.make_smoke_mesh(shape)
    base = LM.bind_4d(mesh)
    V, D = 32, 16
    table = jax.random.normal(jax.random.PRNGKey(2), (V, D)) * 0.1
    toks = jax.random.randint(jax.random.PRNGKey(3), (4, 8), 0, V)
    tspec = base.pspec(base.y, M._names(base.x) + M._names(base.z))

    def run(axes):
        def par(table, toks):
            h = PP.embedding_lookup(toks, table, axes)
            logits = PP.tied_lm_logits(h, table, axes)
            return PP.ar_bwd_identity(
                jnp.sum(logits.astype(jnp.float32) ** 2), axes.y)

        def step(table, toks):
            return jax.value_and_grad(par)(table, toks)

        f = shard_map(step, mesh=mesh, in_specs=(tspec, P(None, None)),
                      out_specs=(P(), tspec), check_vma=False)
        return jax.jit(f)(table, toks)

    vb, gb = run(base)
    vo, go = run(base.with_overlap(ov))
    np.testing.assert_allclose(np.asarray(vb), np.asarray(vo), rtol=2e-5)
    np.testing.assert_allclose(np.asarray(gb), np.asarray(go),
                               rtol=2e-5, atol=1e-5)


def _tp_collective_counts(ov):
    """Collective op counts of one tp_matmul fwd+bwd toy program."""
    mesh = LM.make_smoke_mesh(SHAPE_Z)
    base = LM.bind_4d(mesh)
    axes = base.with_overlap(ov) if ov is not None else base
    x = jax.random.normal(jax.random.PRNGKey(0), (B, S, K))
    w = jax.random.normal(jax.random.PRNGKey(1), (K, N)) * 0.1
    wspec = PP.yz_spec(base, False)
    xspec = base.pspec(base.batch_axes(), None, base.x)

    def loss(x, w):
        y = PP.tp_matmul(x, w, axes, "x", "y")
        return PP.ar_bwd_identity(
            jnp.sum(y.astype(jnp.float32) ** 2),
            M._names(axes.batch_axes()) + M._names(axes.y))

    def step(x, w):
        v, g = jax.value_and_grad(loss, argnums=(0, 1))(x, w)
        return v, g[0], M.psum(g[1], axes.data)

    f = shard_map(step, mesh=mesh, in_specs=(xspec, wspec),
                  out_specs=(P(), xspec, wspec), check_vma=False)
    compiled = jax.jit(f).lower(x, w).compile()
    return RL.parse_collectives(compiled.as_text())


def test_overlap_hlo_uses_collective_permute():
    """Acceptance: the overlapped mode's HLO replaces the monolithic z
    all-gather / reduce-scatter of the matmul path with collective-permute
    chains."""
    blocking = _tp_collective_counts(None)
    ring = _tp_collective_counts(OverlapConfig(
        matmul=True, batched_matmul=True, tied_logits=True))
    # the backward needs the same z-gathered weight as the forward; XLA
    # may keep both gathers or merge them into one, so count at least one
    assert blocking.counts.get("all-gather", 0) >= 1
    assert blocking.counts.get("reduce-scatter", 0) >= 1
    assert blocking.counts.get("collective-permute", 0) == 0
    assert ring.counts.get("all-gather", 0) == 0
    assert ring.counts.get("reduce-scatter", 0) == 0
    # z = 2: one hop per ring. The forward's gather ring and the dW
    # reduce-scatter ring; XLA may give dX its own copy of the gather
    # ring (3 hops) or reuse the forward's (2)
    assert ring.counts.get("collective-permute", 0) >= 2
    # the overlap-aware estimate must see the ring traffic as hideable
    est_b = RL.step_time_estimate(1e9, blocking.bytes_by_kind)
    est_r = RL.step_time_estimate(1e9, ring.bytes_by_kind)
    assert est_r.exposed_comm < est_b.exposed_comm
    assert est_r.hidden_comm > 0.0


@pytest.mark.parametrize("shape", [(1, 1, 2, 2), (1, 1, 2, 1),
                                   (1, 1, 1, 1)], ids=str)
def test_train_step_default_rings_z_where_mapped(shape):
    """Training's default ``TrainOptions`` rings ``tp_matmul``'s z
    collectives wherever z is mapped: one step's loss, gradients (AdamW's
    first moment, unclipped) and updated parameters match the blocking
    schedule (``OverlapConfig()``) within the ring-vs-blocking
    tolerances; with z unmapped the ring is inert and the compiled step
    is the blocking one, metadata aside."""
    from repro.configs import get_config
    from repro.core.partition import spec_tree_to_pspecs
    from repro.launch import steps as ST
    from repro.optim import adamw as OPT

    mesh = LM.make_smoke_mesh(shape)
    axes = LM.bind_4d(mesh)
    cfg = get_config("gpt-paper-20b").reduced()
    params, specs = ST.init_model(cfg, axes, jax.random.PRNGKey(0),
                                  dtype=jnp.float32)
    params = ST.device_put_tree(mesh, params, spec_tree_to_pspecs(specs))
    state = OPT.init_state(params)
    toks = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0,
                              cfg.vocab_size)
    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, axis=1)}
    opt = OPT.AdamWConfig(lr=1e-3, warmup_steps=1, grad_clip=0.0,
                          weight_decay=0.0)

    def step(opts):
        fn, _, _ = ST.make_train_step(cfg, mesh, axes, opt, opts)
        return fn.lower(params, state, batch).compile()

    default = ST.TrainOptions(dtype=jnp.float32)
    assert default.overlap == OverlapConfig(matmul=True)
    ring = step(default)
    blocking = step(dataclasses.replace(default, overlap=OverlapConfig()))
    rt, bt = ring.as_text(), blocking.as_text()
    if shape[3] == 1:
        assert _program(rt) == _program(bt)
        return
    counts = RL.parse_collectives(rt).counts
    assert counts.get("collective-permute", 0) > 0, counts
    # the step donates its state: each schedule steps from its own copy
    (pr, sr, mr), (pb, sb, mb) = (
        fn(*jax.tree.map(jnp.copy, (params, state)), batch)
        for fn in (ring, blocking))
    np.testing.assert_allclose(float(mr["loss"]), float(mb["loss"]),
                               rtol=2e-5)
    treedef = jax.tree.structure(params)

    def grads(s):
        return [st["m"] / (1 - opt.b1)
                for st in treedef.flatten_up_to(s["opt"])]
    for part, r, b in (("grad", grads(sr), grads(sb)),
                       ("param", jax.tree.leaves(pr), jax.tree.leaves(pb))):
        for a, c in zip(r, b, strict=True):
            np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                       rtol=2e-5, atol=1e-5, err_msg=part)


def test_ar_overlap_hlo_replaces_all_reduces():
    """Acceptance (this PR): with ``all_reduce`` on, the x/y activation
    all-reduces of the matmul fwd/bwd also become collective-permute
    chains; only the loss-level psums stay all-reduce."""
    ring_z = _tp_collective_counts(OverlapConfig(
        matmul=True, batched_matmul=True, tied_logits=True))
    ring_xy = _tp_collective_counts(OverlapConfig.all_on())
    # the fwd (over x) and bwd dX (over y) activation all-reduces convert
    # (mapped axes of size > 1 only: x is unmapped on the 4-device shape)
    converts = sum(1 for p in SHAPE_Z[1:3] if p > 1)
    assert (ring_xy.counts.get("all-reduce", 0)
            <= ring_z.counts.get("all-reduce", 0) - converts), (
        ring_z.counts, ring_xy.counts)
    assert (ring_xy.counts.get("collective-permute", 0)
            > ring_z.counts.get("collective-permute", 0))
    assert ring_xy.counts.get("all-gather", 0) == 0
    assert ring_xy.counts.get("reduce-scatter", 0) == 0


# --------------------------------------------------------------------- #
# α-β time model
# --------------------------------------------------------------------- #

def test_time_model_reduces_to_volume_model():
    """With α = 0 and overlap off, exposed comm time == volume * β."""
    layers = CM.transformer_layers(2048, n_layers=4)
    hw = CM.HardwareParams(alpha=0.0)
    for d in [CM.Decomposition(4, 4, 4, 4), CM.Decomposition(16, 4, 4, 1),
              CM.Decomposition(2, 2, 2, 2)]:
        st = CM.predict_step_time(layers, 1 << 18, d, hw)
        want = (CM.model_volume(layers, 1 << 18, d)
                * hw.bytes_per_elem / hw.link_bw)
        assert abs(st.exposed_comm - want) <= 1e-9 * want
        assert st.hidden_comm == 0.0


def test_time_model_conserves_volume_under_overlap():
    """The ring knobs move time from exposed to hidden, never delete it:
    at α = 0, exposed + hidden == volume * β for EVERY overlap config
    (the shared layer_geometry keeps the two models in lockstep)."""
    layers = CM.transformer_layers(2048, n_layers=4)
    hw = CM.HardwareParams(alpha=0.0)
    d = CM.Decomposition(4, 4, 4, 4)
    for ov in [None, OverlapConfig.all_on(),
               OverlapConfig(matmul=True),
               OverlapConfig(all_reduce=True),
               OverlapConfig.all_on(cache_weight_gather=True)]:
        st = CM.predict_step_time(layers, 1 << 18, d, hw, overlap=ov)
        want = (CM.model_volume(layers, 1 << 18, d, overlap=ov)
                * hw.bytes_per_elem / hw.link_bw)
        got = st.exposed_comm + st.hidden_comm
        assert abs(got - want) <= 1e-9 * want, (ov, got, want)


def test_time_model_monotone_in_volume():
    """More volume (same decomposition/hardware) => more exposed time."""
    hw = CM.HardwareParams()
    d = CM.Decomposition(4, 4, 2, 2)
    prev = -1.0
    for h in (512, 1024, 2048, 4096):
        layers = CM.transformer_layers(h)
        st = CM.predict_step_time(layers, 1 << 18, d, hw)
        assert st.exposed_comm > prev
        prev = st.exposed_comm
    # and in tokens at fixed shapes
    layers = CM.transformer_layers(1024)
    prev = -1.0
    for tokens in (1 << 14, 1 << 16, 1 << 18):
        st = CM.predict_step_time(layers, tokens, d, hw)
        assert st.exposed_comm > prev
        prev = st.exposed_comm


def test_overlap_hides_z_traffic_only():
    """The z-only ring knob hides z weight traffic and nothing else."""
    layers = CM.transformer_layers(4096, n_layers=8)
    d = CM.Decomposition(4, 2, 2, 8)
    z_only = OverlapConfig(matmul=True, batched_matmul=True,
                           tied_logits=True)
    blocking = CM.predict_step_time(layers, 1 << 20, d)
    ring = CM.predict_step_time(layers, 1 << 20, d, overlap=z_only)
    assert ring.hidden_comm > 0.0
    assert ring.exposed_comm < blocking.exposed_comm
    # conservation: hiding moves time, it doesn't delete it
    assert (abs((ring.exposed_comm + ring.hidden_comm)
                - blocking.exposed_comm) < 1e-12)
    # z = 1 has nothing to hide under the z-only knob
    d1 = CM.Decomposition(4, 8, 8, 1)
    r1 = CM.predict_step_time(layers, 1 << 20, d1, overlap=z_only)
    assert r1.hidden_comm == 0.0


def test_overlap_hides_activation_all_reduces():
    """The ``all_reduce`` knob hides x/y activation traffic — including
    at g_z = 1, where the z knob has nothing to do — within the compute
    window left over by the z rings."""
    layers = CM.transformer_layers(4096, n_layers=8)
    d1 = CM.Decomposition(4, 8, 8, 1)        # pure tensor-parallel point
    blocking = CM.predict_step_time(layers, 1 << 20, d1)
    ar = CM.predict_step_time(layers, 1 << 20, d1,
                              overlap=OverlapConfig(all_reduce=True))
    assert ar.hidden_comm > 0.0
    assert ar.exposed_comm < blocking.exposed_comm
    assert (abs((ar.exposed_comm + ar.hidden_comm)
                - blocking.exposed_comm) < 1e-12)
    # with both knobs, z traffic claims the window first; total hidden
    # can only grow vs either knob alone
    d = CM.Decomposition(4, 2, 2, 8)
    z_only = CM.predict_step_time(
        layers, 1 << 20, d, overlap=OverlapConfig(matmul=True))
    both = CM.predict_step_time(layers, 1 << 20, d,
                                overlap=OverlapConfig.all_on())
    assert both.hidden_comm >= z_only.hidden_comm
    # and never exceed the overlap-efficiency-scaled compute window
    hw = CM.TPU_V5E
    assert both.hidden_comm <= hw.overlap_efficiency * both.compute + 1e-12


def test_time_model_ranks_eq7_optimum():
    """predict_step_time must rank the paper's Eq. 7 transformer optimum
    (G_c = sqrt(3 G_tensor)) no worse than the volume-only model does on
    the 2D (g_z = 1) closed form."""
    H, tokens = 4096, 1 << 20
    layers = CM.transformer_layers(H, n_layers=24)
    g, g_tensor = 256, 16
    cons = CM.Constraints(min_tensor=g_tensor, z_divides=(1,))

    def best_gy(objective):
        ranked = CM.optimize_decomposition(
            layers, tokens, g, cons, top_k=8, objective=objective,
            include_data_parallel=False)
        cands = [d for d, v in ranked if d.g_tensor == g_tensor]
        assert cands, ranked
        return cands[0].g_y

    want = CM.paper_optimal_gc(g_tensor)  # ~6.93
    vol_err = abs(best_gy("volume") - want)
    time_err = abs(best_gy("time") - want)
    assert time_err <= vol_err, (time_err, vol_err)


def test_layer_volume_overlap_cache_knob():
    """cache_weight_gather drops exactly one AG_z worth of volume."""
    ls = CM.LayerShape(1024, 4096)
    d = CM.Decomposition(2, 2, 2, 4)
    base = CM.layer_volume(ls, 1 << 16, d)
    cached = CM.layer_volume(
        ls, 1 << 16, d,
        overlap=OverlapConfig(cache_weight_gather=True))
    w_full = ls.k * ls.n / (d.g_x * d.g_y)
    ag = CM.gather_or_scatter_volume(d.g_z, w_full)
    assert abs((base - cached) - ag) < 1e-9
