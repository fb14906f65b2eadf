"""Expert parallelism: the 6th mesh axis (g_expert) end to end.

Covers the degeneracy discipline (g_expert = 1 reduces the 6-tuple
comm model AND the layer path bitwise to the 5-axis code), the
all_to_all collective class geometry, the six-way decomposition search,
the mesh/lifecycle plumbing, the capacity-based MoE dispatch across the
expert axis (blocking ``lax.all_to_all`` and the ring-decomposed
``collective_matmul.ring_a2a_expert``), routing parity across
decompositions, and the spec-aware expert-axis gradient sync.

Runs at 4 AND 8 host devices (the CI matrix); device-hungry cases
branch on ``N_DEVICES``.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from conftest import N_DEVICES
from repro.configs import get_config
from repro.core import collective_matmul as CMM
from repro.core import comm_model as CM
from repro.core import mesh as M
from repro.core import parallel as PP
from repro.core.gradsync import GradSyncConfig
from repro.core.overlap import OverlapConfig
from repro.core.partition import ParamSpec, expert_reduce_grads, spec_names
from repro.launch import mesh as LM

EXPERT_NAMES = ("data", "x", "y", "z", "expert")


# ---------------------------------------------------------------------- #
# comm model: the all_to_all class and the g_expert = 1 degeneracy
# ---------------------------------------------------------------------- #

def test_decomposition_six_tuple_defaults():
    d = CM.Decomposition(2, 2, 2, 1)
    assert d.g_expert == 1 and d.g_seq == 1
    assert d.g == 8 and d.g_tensor == 4
    d6 = CM.Decomposition(2, 2, 2, 1, 1, 2)
    assert d6.g == 16            # expert joins the device budget...
    assert d6.g_tensor == 4      # ...but not the tensor (memory) floor


def test_all_to_all_volume_and_time_geometry():
    assert CM.all_to_all_volume(1, 4096.0) == 0.0
    assert CM.all_to_all_volume(4, 4096.0) == 3.0 / 4.0 * 4096.0
    hw = CM.HardwareParams(alpha=1e-6, gamma=2e-6, link_bw=1e9,
                           bytes_per_elem=4.0)
    p, buf = 4, 4096.0
    t = CM.collective_time("all_to_all", p, buf, hw)
    expect = (hw.gamma + hw.alpha * (p - 1)
              + CM.all_to_all_volume(p, buf) * hw.bytes_per_elem
              / hw.link_bw)
    assert t == expect
    assert CM.collective_time("all_to_all", 1, buf, hw) == 0.0
    with pytest.raises(ValueError):
        CM.collective_time("gossip", 4, buf, hw)


def test_expert_identity_markers_are_inert():
    """At g_expert = 1 the MoE markers (expert=True, a2a_width) change
    NOTHING — the 6-tuple model is the 5-tuple model bitwise."""
    marked = [CM.LayerShape(64, 256, expert=True, a2a_width=16.0),
              CM.LayerShape(256, 64, transposed=True, expert=True),
              CM.LayerShape(64, 192, kv_ring_width=32.0)]
    plain = [dataclasses.replace(ls, expert=False, a2a_width=0.0)
             for ls in marked]
    for d in (CM.Decomposition(2, 2, 2, 1),
              CM.Decomposition(1, 2, 2, 2, 2),
              CM.Decomposition(4, 1, 2, 1, 1, 1)):
        assert (CM.model_volume(marked, 4096, d)
                == CM.model_volume(plain, 4096, d))
        for ov in (None, OverlapConfig(expert_a2a=True),
                   OverlapConfig.all_on()):
            tm = CM.predict_step_time(marked, 4096, d, overlap=ov)
            tp_ = CM.predict_step_time(plain, 4096, d, overlap=ov)
            assert tm == tp_


def test_layer_volume_expert_a2a_term():
    """Hand-check: an isolated expert axis pays exactly 4 all_to_all
    passes of the dispatch buffer and nothing else."""
    ls = CM.LayerShape(8, 8, expert=True, a2a_width=16.0)
    d = CM.Decomposition(1, 1, 1, 1, 1, 4)
    v = CM.layer_volume(ls, 64, d, include_data_parallel=False)
    m_local = 64 / 4                       # tokens / g_expert
    assert v == 4.0 * CM.all_to_all_volume(4, m_local * 16.0)


def test_expert_bank_weight_sharding_and_grad_sync():
    """The expert bank co-shards over g_expert (weight buffers shrink);
    dense params replicate and pay an expert-axis grad all-reduce."""
    d = CM.Decomposition(1, 1, 1, 1, 1, 4)
    dense = CM.LayerShape(64, 128)
    bank = CM.LayerShape(64, 128, expert=True)
    g_dense = CM.layer_geometry(dense, 64, d)
    g_bank = CM.layer_geometry(bank, 64, d)
    assert g_bank.w_full_per_xy == g_dense.w_full_per_xy / 4
    assert g_bank.dp_buf == g_dense.dp_buf / 4
    # dense: the only nonzero term is the expert-axis grad all-reduce
    assert (CM.layer_volume(dense, 64, d)
            == CM.allreduce_volume(4, 64 * 128))
    # bank: grads already live on their own expert shard — no sync at all
    assert CM.layer_volume(bank, 64, d) == 0.0


def test_enumeration_expert_gated_and_divisibility():
    default = list(CM.enumerate_decompositions(16))
    assert len(default) == 35                     # the 5-tuple pin holds
    assert all(d.g_expert == 1 for d in default)
    c = CM.Constraints(max_expert=4, expert_divides=(8,), global_batch=8)
    opened = list(CM.enumerate_decompositions(16, c))
    assert {d.g_expert for d in opened} >= {1, 2, 4}
    for d in opened:
        assert d.g == 16
        assert d.g_expert <= 4 and 8 % d.g_expert == 0
        assert 8 % (d.g_data * d.g_z * d.g_expert) == 0


@pytest.mark.parametrize("objective", ["volume", "time"])
def test_optimizer_picks_expert_on_moe_heavy_profile(objective):
    """A constructed profile where every classic axis is expensive (big
    expert-bank weights, few tokens) and the a2a is cheap: the six-way
    search must spend the whole budget on g_expert."""
    layers = [CM.LayerShape(1024, 8192, expert=True, a2a_width=8.0),
              CM.LayerShape(8192, 1024, transposed=True, expert=True)]
    c = CM.Constraints(max_expert=8, expert_divides=(8,))
    kw = dict(objective=objective)
    best, _ = CM.optimize_decomposition(layers, 256, 8, c, **kw)[0]
    if objective == "volume":
        assert best.g_expert == 8, best     # pure expert moves least data
    else:
        # the α term penalizes deep a2a rings, so time may split the
        # budget with y — but the search must still open the axis
        assert best.g_expert > 1, best
    # capping the axis falls back to a 5-tuple plan, no error
    best5, _ = CM.optimize_decomposition(
        layers, 256, 8, CM.Constraints(max_expert=1), **kw)[0]
    assert best5.g_expert == 1


def test_time_model_expert_overlap_conserves_volume():
    """OverlapConfig.expert_a2a moves a2a time from exposed to hidden;
    it never creates or destroys communication."""
    layers = [CM.LayerShape(512, 2048, expert=True, a2a_width=64.0)]
    d = CM.Decomposition(1, 1, 1, 1, 1, 4)
    t_no = CM.predict_step_time(layers, 4096, d,
                                include_data_parallel=False)
    t_ov = CM.predict_step_time(layers, 4096, d,
                                overlap=OverlapConfig(expert_a2a=True),
                                include_data_parallel=False)
    assert t_no.hidden_comm == 0.0
    assert t_ov.hidden_comm > 0.0
    assert np.isclose(t_ov.exposed_comm + t_ov.hidden_comm,
                      t_no.exposed_comm, rtol=0, atol=1e-18)
    assert t_ov.compute == t_no.compute


# ---------------------------------------------------------------------- #
# mesh + lifecycle plumbing
# ---------------------------------------------------------------------- #

def test_bind_expert_axis():
    mesh = LM.make_smoke_mesh((1, 2, 1, 1, 2), EXPERT_NAMES)
    axes = LM.bind_4d(mesh)
    assert axes.gexpert == 2 and axes.expert == "expert"
    assert axes.batch_shards == 2           # data(1) * z(1) * expert(2)
    assert "expert" in axes.batch_axes()
    assert "expert" in axes.all_names()
    assert axes.axis("expert") == "expert"
    # the 4-axis binding stays expert-free (size-1 ⇒ None)
    mesh4 = LM.make_smoke_mesh((1, 2, 1, 1))
    axes4 = LM.bind_4d(mesh4)
    assert axes4.expert is None and axes4.gexpert == 1


def test_lifecycle_six_factors_shrink_then_grow():
    life = LM.MeshLifecycle(2, 1, 1, 1, g_expert=2)
    assert life.factors == (2, 1, 1, 1, 1, 2)
    assert life.required == 4 and life.tensor == 2
    mesh, axes = life.build()
    assert "expert" in mesh.axis_names and axes.gexpert == 2
    life.mark_failed(2)
    plan = life.replan(global_batch=8)
    assert plan["g_expert"] == 2            # tensor factors never shrink

    def best_gd(surviving):
        # largest g_data fitting the pool AND the batch-divisibility
        # rule: global_batch % (g_data * g_z * g_expert * od) == 0
        return max(gd for gd in range(1, surviving // 2 + 1)
                   if 8 % (gd * 2) == 0)

    shrunk = best_gd(N_DEVICES - 2)
    assert plan["g_data"] == shrunk
    life.mark_recovered()                   # the elastic grow path
    plan = life.replan(global_batch=8)
    assert plan["g_data"] == N_DEVICES // 2 and plan["g_expert"] == 2
    assert plan["g_data"] > shrunk


def test_all_to_all_blocking_and_ring_agree():
    p = 4
    mesh = LM.make_smoke_mesh((p,), ("expert",))
    x = jnp.arange(p * p * 3, dtype=jnp.float32).reshape(p * p, 3)

    def body(v):
        return (M.all_to_all(v, "expert", dim=0),
                M.ring_all_to_all(v, "expert", dim=0))

    blk, ring = shard_map(body, mesh=mesh, in_specs=P("expert"),
                          out_specs=(P("expert"), P("expert")),
                          check_vma=False)(x)
    # reference: global row r*p+s of the output is input row s*p+r
    ref = np.asarray(x).reshape(p, p, 3).swapaxes(0, 1).reshape(p * p, 3)
    np.testing.assert_array_equal(np.asarray(blk), ref)
    np.testing.assert_array_equal(np.asarray(ring), ref)


def test_ring_a2a_expert_matches_blocking_roundtrip():
    """ring_a2a_expert == all_to_all -> per-block FFN -> all_to_all,
    bitwise, including a rank-dependent FFN (the expert weights)."""
    p = 4
    mesh = LM.make_smoke_mesh((p,), ("expert",))
    buf = jax.random.normal(jax.random.PRNGKey(0), (p * p, 3, 2))

    def body(b):                            # b: (p, C, d) per rank
        r = jax.lax.axis_index("expert").astype(jnp.float32)

        def ffn(block):                     # (C, d) -> (C, d)
            return block * (r + 1.0) + r

        ring = CMM.ring_a2a_expert(b, "expert", ffn)
        recv = M.all_to_all(b, "expert", dim=0)
        blk = M.all_to_all(jax.vmap(ffn)(recv), "expert", dim=0)
        return ring, blk

    ring, blk = shard_map(body, mesh=mesh, in_specs=P("expert"),
                          out_specs=(P("expert"), P("expert")),
                          check_vma=False)(buf)
    np.testing.assert_array_equal(np.asarray(ring), np.asarray(blk))
    assert not np.allclose(np.asarray(ring), np.asarray(buf))


def test_ring_a2a_expert_rejects_bad_leading_dim():
    p = 2
    mesh = LM.make_smoke_mesh((p,), ("expert",))
    buf = jnp.zeros((p * 3, 4, 2))          # dim 0 != p per rank

    def body(b):
        return CMM.ring_a2a_expert(b, "expert", lambda x: x)

    with pytest.raises(ValueError, match="expert-axis ring size"):
        shard_map(body, mesh=mesh, in_specs=P("expert"),
                  out_specs=P("expert"), check_vma=False)(buf)


# ---------------------------------------------------------------------- #
# MoE layer: dispatch bookkeeping, routing parity, end-to-end parity
# ---------------------------------------------------------------------- #

def _dispatch(idx, gates, e_block, capacity, n_tok, top_k):
    """The capacity bookkeeping of layers/moe.moe_apply, verbatim."""
    eflat = jnp.where((idx >= 0) & (idx < e_block), idx, e_block)
    onehot = jax.nn.one_hot(eflat.reshape(-1), e_block + 1,
                            dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) - 1
    pos = jnp.take_along_axis(pos, eflat.reshape(-1, 1), axis=1)[:, 0]
    fits = (pos < capacity) & (eflat.reshape(-1) < e_block)
    slot = jnp.where(fits, eflat.reshape(-1) * capacity + pos,
                     e_block * capacity)
    tok_ids = jnp.tile(jnp.arange(n_tok)[:, None], (1, top_k)).reshape(-1)
    owner = jnp.zeros(e_block * capacity + 1, jnp.int32).at[slot].set(
        tok_ids, mode="drop")[:-1]
    filled = jnp.zeros(e_block * capacity + 1, jnp.bool_).at[slot].set(
        True, mode="drop")[:-1]
    gate_of = jnp.zeros(e_block * capacity + 1, jnp.float32).at[slot].set(
        gates.reshape(-1), mode="drop")[:-1]
    return owner, filled, gate_of, fits


def test_capacity_overflow_drop_determinism():
    """Overflowing an expert queue drops the HIGHEST flattened
    (token, slot) indices — deterministically, run to run."""
    n_tok, top_k, e_block, capacity = 8, 1, 2, 3
    idx = jnp.zeros((n_tok, top_k), jnp.int32)      # all -> expert 0
    gates = jnp.linspace(0.1, 0.8, n_tok).reshape(n_tok, top_k)
    a = _dispatch(idx, gates, e_block, capacity, n_tok, top_k)
    b = _dispatch(idx, gates, e_block, capacity, n_tok, top_k)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    owner, filled, gate_of, fits = a
    # first `capacity` tokens keep their slots, in order
    np.testing.assert_array_equal(np.asarray(owner[:capacity]),
                                  np.arange(capacity))
    assert bool(filled[:capacity].all())
    assert not bool(filled[capacity:].any())        # expert 1 untouched
    np.testing.assert_array_equal(
        np.asarray(fits), np.arange(n_tok) < capacity)
    np.testing.assert_array_equal(np.asarray(gate_of[:capacity]),
                                  np.asarray(gates[:capacity, 0]))


def _router_outputs(shape, names=("data", "x", "y", "z")):
    """Router gates/indices/aux on one mesh decomposition (the
    moe_apply front half, shard_map'ped)."""
    from repro.layers import moe as MOE

    cfg = get_config("deepseek-v2-lite-16b").reduced()
    mc = cfg.moe
    mesh = LM.make_smoke_mesh(shape, names)
    axes = LM.bind_4d(mesh)
    w = PP.tp_linear_init(jax.random.PRNGKey(7), cfg.d_model,
                          mc.n_experts, axes, in_shard="x",
                          out_shard=None, dtype=jnp.float32)
    hf = jax.random.normal(jax.random.PRNGKey(8), (16, cfg.d_model))

    def body(h, wv):
        logits = PP.tp_matmul(h, wv, axes, "x", None).astype(jnp.float32)
        gates, idx = MOE._topk_gates(logits, mc)
        return gates, idx, MOE._aux_losses(logits, idx, mc)

    fn = shard_map(body, mesh=mesh, in_specs=(P(None, "x"), w.spec),
                   out_specs=(P(), P(), P()), check_vma=False)
    gates, idx, aux = fn(hf, w.value)
    return np.asarray(gates), np.asarray(idx), float(aux)


def test_routing_parity_across_decompositions():
    """Satellite: gates, top-k indices and aux losses are bitwise
    identical across (data, y, z) re-decompositions of the same device
    count — routing depends on the x contraction only."""
    variants = [(1, 2, 2, 1), (1, 2, 1, 2), (2, 2, 1, 1)]
    if N_DEVICES >= 8:
        variants.append((1, 2, 2, 2))
    ref = _router_outputs(variants[0])
    for shape in variants[1:]:
        gates, idx, aux = _router_outputs(shape)
        np.testing.assert_array_equal(gates, ref[0], err_msg=str(shape))
        np.testing.assert_array_equal(idx, ref[1], err_msg=str(shape))
        assert aux == ref[2], shape


def _train_losses(shape, names=None, overlap=None, steps=3, B=8, S=32):
    from repro.core.partition import spec_tree_to_pspecs
    from repro.launch import steps as ST
    from repro.optim.adamw import AdamWConfig, init_state

    cfg = get_config("deepseek-v2-lite-16b").reduced()
    # a capacity no expert queue can overflow (tokens x top_k / E slots
    # times E / top_k): the expert axis's capacity dispatch then drops
    # nothing, as the dropless g_expert = 1 dispatch never does
    mc = cfg.moe
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
        mc, capacity_factor=mc.n_experts / mc.top_k))
    mesh = LM.make_smoke_mesh(
        shape, names or ("data", "x", "y", "z")[:len(shape)])
    axes = LM.bind_4d(mesh)
    params, specs = ST.init_model(cfg, axes, jax.random.PRNGKey(0),
                                  dtype=jnp.float32)
    params = ST.device_put_tree(mesh, params, spec_tree_to_pspecs(specs))
    state = init_state(params)
    step_fn, _, _ = ST.make_train_step(
        cfg, mesh, axes,
        AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=50),
        ST.TrainOptions(overdecompose=1, dtype=jnp.float32,
                        overlap=overlap or OverlapConfig()))
    rng = np.random.RandomState(0)
    batch = {"tokens": jnp.asarray(
        rng.randint(0, cfg.vocab_size, (B, S)), jnp.int32),
        "labels": jnp.asarray(
        rng.randint(0, cfg.vocab_size, (B, S)), jnp.int32)}
    losses = []
    for _ in range(steps):
        params, state, metrics = step_fn(params, state, batch)
        losses.append(float(metrics["loss"]))
        assert np.isfinite(losses[-1])
    return losses


# The expert axis moves the MoE combine and the gradient sums onto other
# devices, and XLA may order those fp32 reductions differently; the losses
# then differ in the last bits (1 ulp, 1.7e-7 relative, was observed).
# 1e-6 relative is ~8 fp32 ulps: far below any real routing or sharding
# error, which moves the loss in its third digit.
LOSS_RTOL = 1e-6


def _parity_shapes():
    """(baseline, expert) shapes holding the token shards fixed: the
    expert axis replaces one factor of g_data, so dense layers see the
    identical batch split and losses must match to ``LOSS_RTOL``."""
    if N_DEVICES >= 8:
        return (2, 2, 2, 1), (1, 2, 2, 1, 2)
    return (2, 2, 1, 1), (1, 2, 1, 1, 2)


def test_expert_blocking_parity_with_data_axis():
    base, ex = _parity_shapes()
    l_base = _train_losses(base)
    l_blk = _train_losses(ex, EXPERT_NAMES)
    np.testing.assert_allclose(l_blk, l_base, rtol=LOSS_RTOL)
    assert l_base[-1] < l_base[0]           # it actually trains


def test_expert_ring_parity_with_blocking():
    _, ex = _parity_shapes()
    l_blk = _train_losses(ex, EXPERT_NAMES)
    l_ring = _train_losses(ex, EXPERT_NAMES,
                           overlap=OverlapConfig(expert_a2a=True))
    np.testing.assert_allclose(l_ring, l_blk, rtol=LOSS_RTOL)


def test_moe_init_rejects_nondividing_expert_axis():
    from repro.layers import moe as MOE

    cfg = get_config("deepseek-v2-lite-16b").reduced()
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, n_experts=6))
    axes = M.MeshAxes(y="y", expert="expert",
                      sizes=(("y", 2), ("expert", 2)))
    with pytest.raises(ValueError, match="not divisible"):
        MOE.moe_init(jax.random.PRNGKey(0), cfg, axes)


# ---------------------------------------------------------------------- #
# gradient sync, param layout, step-builder guard, calibration
# ---------------------------------------------------------------------- #

def test_expert_reduce_grads_is_spec_aware():
    axes = M.MeshAxes(expert="expert", sizes=(("expert", 2),))
    specs = {"dense": ParamSpec(P(None, "x"), z_reduced=True),
             "bank": ParamSpec(P(("y", "expert"), "x", None),
                               z_reduced=True)}
    grads = {"dense": jnp.ones(3), "bank": jnp.ones(3)}
    synced = []

    def psum_fn(g, ax):
        synced.append(ax)
        return g + 1.0

    out = expert_reduce_grads(grads, specs, axes, psum_fn)
    assert synced == ["expert"]             # dense only
    np.testing.assert_array_equal(np.asarray(out["dense"]),
                                  np.full(3, 2.0))
    np.testing.assert_array_equal(np.asarray(out["bank"]),
                                  np.ones(3))


def test_spec_names_flattens_tuples():
    assert spec_names(P(("y", "expert"), "x", None)) == ("y", "expert",
                                                        "x")
    assert spec_names(ParamSpec(P(None, "z"), z_reduced=True)) == ("z",)


def test_tp_expert_init_shards_bank_over_y_and_expert():
    mesh = LM.make_smoke_mesh((1, 1, 2, 1, 2), EXPERT_NAMES)
    axes = LM.bind_4d(mesh)
    b = PP.tp_expert_init(jax.random.PRNGKey(0), 4, 8, 8, axes,
                          abstract=True)
    assert set(spec_names(b.spec)) >= {"y", "expert"}
    # without the expert axis the layout is today's y-only placement
    mesh4 = LM.make_smoke_mesh((1, 1, 2, 1), ("data", "x", "y", "z"))
    b4 = PP.tp_expert_init(jax.random.PRNGKey(0), 4, 8, 8,
                           LM.bind_4d(mesh4), abstract=True)
    assert "expert" not in spec_names(b4.spec)
    assert "y" in spec_names(b4.spec)


def test_make_train_step_guards_expert_with_sharded_gradsync():
    from repro.launch import steps as ST
    from repro.optim.adamw import AdamWConfig

    cfg = get_config("deepseek-v2-lite-16b").reduced()
    mesh = LM.make_smoke_mesh((1, 2, 1, 1, 2), EXPERT_NAMES)
    axes = LM.bind_4d(mesh)
    with pytest.raises(NotImplementedError, match="expert parallelism"):
        ST.make_train_step(
            cfg, mesh, axes, AdamWConfig(lr=1e-3, total_steps=10),
            ST.TrainOptions(overdecompose=1, dtype=jnp.float32,
                            gradsync=GradSyncConfig(zero=True)))


def test_calibrate_measures_all_to_all_class():
    from repro.core import calibrate as CA

    mesh = LM.make_smoke_mesh((2,), ("expert",))
    samples = CA.measure_axis(mesh, "expert", [512], reps=1)
    a2a = [s for s in samples if s.kind == "all_to_all"]
    assert len(a2a) == 1
    s = a2a[0]
    assert s.p == 2 and s.steps == 1
    assert s.wire_bytes == 0.5 * 512 * 4    # (p-1)/p * buf, fp32
    assert s.seconds >= 0.0
    CA.fit_constants(samples)               # the fitter accepts the class
