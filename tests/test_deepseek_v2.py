"""DeepSeek-V2's mechanisms in the program, small and seeded on the CPU:
a chip's share of the routed experts against the uncut layer, the
published gate and sequence-wise balance loss against hand-written
copies of their formulas, dropless dispatch under a skew no capacity
would hold, and YaRN's frequencies and softmax scale against hand
values of the published configuration."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.core.partition import spec_tree_to_pspecs, unbox
from repro.launch import mesh as LM
from repro.layers import attention as A
from repro.layers import moe as MOE
from repro.layers import rotary as R

DSV2 = get_config("deepseek-v2-lite-16b")


def _moe_cfg(n_held=0, **moe):
    """The published router (64 experts, top-6, softmax gates not
    renormalised, sequence-wise loss) at small widths; the balance
    factor large enough to see."""
    mc = dataclasses.replace(DSV2.moe, d_expert=16, n_held=n_held,
                             aux_loss_coef=0.1, **moe)
    return dataclasses.replace(DSV2, d_model=32, moe=mc)


def _one_chip():
    mesh = LM.make_smoke_mesh((1, 1, 1, 1))
    return mesh, LM.bind_4d(mesh)


def _apply(cfg, params, h):
    """moe_apply on one device: (out, aux, counters)."""
    mesh, axes = _one_chip()
    _, specs = unbox(MOE.moe_init(jax.random.PRNGKey(0), cfg, axes,
                                  abstract=True))
    fn = shard_map(lambda p, x: MOE.moe_apply(p, x, cfg, axes), mesh=mesh,
                   in_specs=(spec_tree_to_pspecs(specs), P()),
                   out_specs=(P(), P(), P()), check_vma=False)
    return jax.jit(fn)(params, h)


def _params(cfg, seed=0):
    mesh, axes = _one_chip()
    p, _ = unbox(MOE.moe_init(jax.random.PRNGKey(seed), cfg, axes,
                              dtype=jnp.float32))
    # a router with logits of order 1, so routing spreads over experts
    p["w_router"] = p["w_router"] * 3.0
    return p


def _tokens(cfg, B=2, T=16, seed=1):
    return jax.random.normal(jax.random.PRNGKey(seed), (B, T, cfg.d_model))


def test_chip_share_adds_up_to_the_uncut_layer():
    """(a) For each block of 8 of the 64 experts, the router's columns
    permuted so that the block comes first and the layer told it holds
    8: the blocks' routed parts add up, with the shared experts counted
    once, to the uncut 64-expert layer, and every block's balance loss
    is the uncut one (the router is the same router)."""
    full = _moe_cfg()
    part = _moe_cfg(n_held=8)
    p = _params(full)
    h = _tokens(full)
    want, want_aux, _ = _apply(full, p, h)
    shared = _shared(p, h)
    total = shared
    for j in range(8):
        block = np.arange(8 * j, 8 * j + 8)
        perm = np.concatenate([block, np.delete(np.arange(64), block)])
        pj = dict(p, w_router=p["w_router"][:, perm], w_up=p["w_up"][block],
                  w_down=p["w_down"][block])
        out, aux, counters = _apply(part, pj, h)
        np.testing.assert_allclose(float(aux), float(want_aux), rtol=1e-6)
        assert float(counters[2]) == 0.0
        total = total + (out - shared)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    # the shares differ: each block holds its own part of the result
    assert float(jnp.linalg.norm(want - shared)) > 0.1 * float(
        jnp.linalg.norm(want))


def _published_gates(logits, k):
    """softmax over all experts, greedy top-k, not renormalised."""
    z = logits - logits.max(-1, keepdims=True)
    probs = np.exp(z) / np.exp(z).sum(-1, keepdims=True)
    idx = np.argsort(-probs, axis=-1, kind="stable")[:, :k]
    return np.take_along_axis(probs, idx, -1), idx, probs


def _published_seq_aux(probs, idx, n_seq, alpha):
    """alpha * mean_b sum_e ce_e * mean_t p_{t,e}, ce_e = count_e /
    (T k / E), one sequence at a time."""
    n, E = probs.shape
    T, k = n // n_seq, idx.shape[1]
    out = 0.0
    for b in range(n_seq):
        rows = slice(b * T, (b + 1) * T)
        count = np.zeros(E)
        for e in idx[rows].ravel():
            count[e] += 1
        ce = count / (T * k / E)
        out += np.sum(ce * probs[rows].mean(0))
    return alpha * out / n_seq


@pytest.mark.parametrize("spread", [3.0, 0.05])
def test_gates_and_sequence_loss_are_the_published_ones(spread):
    """(b) The DeepSeek-V2 gate and balance loss against hand-written
    copies; at a small logit spread the top-6 of 64 gates sum well below
    1 (they are not renormalised)."""
    mc = dataclasses.replace(DSV2.moe, aux_loss_coef=0.001)
    n_seq, T = 3, 10
    logits = np.asarray(jax.random.normal(jax.random.PRNGKey(2),
                                          (n_seq * T, 64))) * spread
    gates, idx = MOE._topk_gates(jnp.asarray(logits, jnp.float32), mc)
    want_g, want_i, probs = _published_gates(logits, mc.top_k)
    np.testing.assert_array_equal(np.asarray(idx), want_i)
    np.testing.assert_allclose(np.asarray(gates), want_g, rtol=1e-5)
    if spread < 1:
        assert float(jnp.max(jnp.sum(gates, -1))) < 0.2
    assert float(jnp.min(jnp.sum(gates, -1))) < 1.0 - 1e-3
    aux = MOE._aux_losses(jnp.asarray(logits, jnp.float32), idx, mc, n_seq)
    np.testing.assert_allclose(
        float(aux), _published_seq_aux(probs, want_i, n_seq, 0.001),
        rtol=1e-5)
    # the sequences count one by one: the batch-wide loss differs
    one = MOE._aux_losses(jnp.asarray(logits, jnp.float32), idx, mc, 1)
    assert abs(float(one) - float(aux)) > 1e-3 * float(aux)


def test_renormalised_gates_stay_as_they_were():
    """Configs that renormalise (Mixtral-style ``norm_topk``) keep the
    softmax over the top-k logits, summing to 1."""
    mc = dataclasses.replace(DSV2.moe, norm_topk=True)
    logits = jax.random.normal(jax.random.PRNGKey(3), (8, 64))
    gates, idx = MOE._topk_gates(logits, mc)
    vals = jnp.take_along_axis(logits, idx, -1)
    np.testing.assert_allclose(np.asarray(gates),
                               np.asarray(jax.nn.softmax(vals, -1)),
                               rtol=1e-6)


def _shared(p, h):
    sh = p["shared"]
    return (jax.nn.silu(h @ sh["wg"]) * (h @ sh["wi"])) @ sh["wo"]


def _dense_loop(cfg, p, h):
    """The held experts one by one on every token, weighted by the gate
    of each slot that chose them, plus the shared experts."""
    hf = h.reshape(-1, cfg.d_model)
    gates, idx = MOE._topk_gates(hf @ p["w_router"], cfg.moe)
    f = cfg.moe.d_expert
    out = jnp.zeros_like(hf)
    for e in range(p["w_up"].shape[0]):
        u = hf @ p["w_up"][e]
        eo = (jax.nn.silu(u[:, :f]) * u[:, f:]) @ p["w_down"][e]
        w = jnp.sum(jnp.where(idx == e, gates, 0.0), -1)
        out = out + eo * w[:, None]
    return out.reshape(h.shape) + _shared(p, h)


def test_no_drops_when_every_token_picks_the_same_experts():
    """(d) Every token routed to held experts 0..5, expert 0 first: a
    load of every token on one expert, far past any capacity (the
    expert axis's dispatch, at capacity factor 1.25, would keep 4 of its
    32 slots), still gives the dense loop's output; no slot is
    dropped."""
    cfg = _moe_cfg(n_held=8)
    p = _params(cfg)
    h = jnp.abs(_tokens(cfg)) + 1.0
    bias = jnp.zeros((cfg.d_model, 64)).at[:, :6].set(
        jnp.linspace(2.0, 1.0, 6))
    p = dict(p, w_router=p["w_router"] * 0.01 + bias)
    out, _, counters = _apply(cfg, p, h)
    _, idx = MOE._topk_gates(h.reshape(-1, cfg.d_model) @ p["w_router"],
                             cfg.moe)
    assert set(np.unique(np.asarray(idx))) == set(range(6))
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_dense_loop(cfg, p, h)),
                               rtol=1e-4, atol=1e-5)
    held, max_mean, dropped = (float(x) for x in counters)
    assert held == 1.0 and dropped == 0.0
    assert max_mean == pytest.approx(8 / 6)


def test_grouped_products_refuse_the_ring_overlap():
    """The dropless path's grouped products run the blocking schedule
    only: an overlap setting that would ring their z or x collectives is
    refused when the layer is traced, not silently ignored."""
    from repro.core import parallel as PP
    from repro.core.overlap import OverlapConfig
    axes = LM.bind_4d(LM.make_smoke_mesh((1, 2, 1, 2)))
    x, w = jnp.zeros((8, 16)), jnp.zeros((2, 16, 32))
    sizes = jnp.array([4, 4], jnp.int32)
    for ov in (OverlapConfig(batched_matmul=True),
               OverlapConfig(all_reduce=True)):
        with pytest.raises(NotImplementedError, match="overlap"):
            PP.tp_grouped_matmul(x, w, sizes, axes.with_overlap(ov), "x",
                                 None)


def test_train_step_reports_the_counters():
    """The counters of every MoE layer ride in the train step's metrics:
    the held share near 8/64 for a chip's share, nothing dropped."""
    from repro.launch import steps as ST
    from repro.optim.adamw import AdamWConfig, init_state

    cfg = dataclasses.replace(
        DSV2.reduced(), n_layers=3,
        moe=dataclasses.replace(DSV2.moe, n_held=8, d_expert=32))
    mesh, axes = _one_chip()
    params, specs = ST.init_model(cfg, axes, jax.random.PRNGKey(0),
                                  dtype=jnp.float32)
    step, _, _ = ST.make_train_step(
        cfg, mesh, axes, AdamWConfig(lr=1e-3, total_steps=10),
        ST.TrainOptions(overdecompose=2, dtype=jnp.float32))
    tok = jax.random.randint(jax.random.PRNGKey(1), (4, 64), 0,
                             cfg.vocab_size)
    _, _, m = step(params, init_state(params),
                   {"tokens": tok, "labels": tok})
    n_moe = cfg.ffns().count("moe")
    assert n_moe == 2
    for k in ("moe_held_share", "moe_load_max_mean", "moe_dropped"):
        assert m[k].shape == (n_moe,), k
    assert np.all(np.asarray(m["moe_dropped"]) == 0)
    share = np.asarray(m["moe_held_share"])
    assert np.all((share > 0.03) & (share < 0.4)), share
    assert np.all(np.asarray(m["moe_load_max_mean"]) >= 1.0)


def test_yarn_frequencies_and_scale_are_the_published_ones():
    """(c) DeepSeek-V2-Lite's rope_scaling (factor 40 over 4096
    positions, beta 32 / 1, mscale 0.707): over the 64-wide rotary part
    the correction range is pairs 10 to 23 of 32; the frequencies below
    it are theta^(-2i/64), above it divided by 40, and linearly blended
    inside; MLA's softmax scale is mscale^2 / sqrt(192) with mscale =
    0.1 * 0.707 * ln 40 + 1."""
    y = DSV2.rope_scaling
    assert R.yarn_correction_range(y, 64, 10000.0) == (10, 23)
    inv, rot = R.rope_freqs(64, 10000.0, yarn=y)
    base = 1.0 / 10000.0 ** (np.arange(0, 64, 2) / 64)
    ramp = np.clip((np.arange(32) - 10) / 13.0, 0.0, 1.0)
    want = base / 40 * ramp + base * (1 - ramp)
    assert rot == 64
    np.testing.assert_allclose(np.asarray(inv), want, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(inv[:11]), base[:11], rtol=1e-6)
    np.testing.assert_allclose(np.asarray(inv[23:]), base[23:] / 40,
                               rtol=1e-6)
    mscale = 0.1 * 0.707 * math.log(40) + 1
    assert R.yarn_mscale(40.0, 0.707) == pytest.approx(1.2608, abs=1e-4)
    assert A.mla_scale(DSV2) == pytest.approx(mscale ** 2 / math.sqrt(192),
                                              rel=1e-12)
    assert A.mla_scale(DSV2) == pytest.approx(0.114721, abs=5e-7)


def test_yarn_rotates_interleaved_pairs():
    """Pair (2i, 2i + 1) turns by position * inv_freq[i]: a unit vector
    on pair i comes back at angle position * inv[i]."""
    y = DSV2.rope_scaling
    inv, _ = R.rope_freqs(64, 10000.0, yarn=y)
    i, pos = 20, 3000
    x = jnp.zeros((1, 1, 1, 64)).at[..., 2 * i].set(1.0)
    out = R.apply_rope(x, jnp.full((1, 1), pos, jnp.int32), 10000.0,
                       yarn=y)
    ang = pos * float(inv[i])
    np.testing.assert_allclose(
        np.asarray(out[0, 0, 0, 2 * i:2 * i + 2]),
        [math.cos(ang), math.sin(ang)], atol=2e-3)
    assert float(jnp.sum(jnp.abs(out))) == pytest.approx(
        abs(math.cos(ang)) + abs(math.sin(ang)), abs=2e-3)
