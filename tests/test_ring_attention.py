"""Context parallelism (PR 6): striped ring attention over the ``seq``
mesh axis.

Covers the contract at every layer: the Pallas partial-block flash kernel
vs its oracle (including chained blocks, non-dividing lengths and strided
global positions), the striped layout helpers, ``seq_attn`` parity vs the
single-device core for g_seq in {1, 2, 4} under both the blocking-gather
and ring schedules, the HLO guarantee (ring mode lowers the KV exchange
to collective-permute chains with NO all-gather of the full sequence),
end-to-end train-loss parity vs an unsharded decomposition (exercising
the seq-axis gradient reductions), the comm model's ring_exchange
collective class and its g_seq=1 bitwise degeneracy, the satellite ring
embedding gather, and the fp32-softmax dtype pin."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from conftest import N_DEVICES
from repro.core import mesh as M
from repro.core import parallel as PP
from repro.core.overlap import OverlapConfig
from repro.kernels import ops
from repro.layers import attention as A
from repro.launch import mesh as LM


def _qkv_bhtd(T, S, hq=4, hkv=2, d=32, seed=0):
    """Kernel-layout (B, H, T, D) tensors."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (1, hq, T, d)),
            jax.random.normal(ks[1], (1, hkv, S, d)),
            jax.random.normal(ks[2], (1, hkv, S, d)))


def _qkv_bthd(T, hq=4, hkv=2, d=16, B=2, seed=0):
    """Layer-layout (B, T, H, D) tensors."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (B, T, hq, d)),
            jax.random.normal(ks[1], (B, T, hkv, d)),
            jax.random.normal(ks[2], (B, T, hkv, d)))


def _partial_init(B, hq, T, d):
    return (jnp.full((B, hq, T), A.NEG_INF, jnp.float32),
            jnp.zeros((B, hq, T), jnp.float32),
            jnp.zeros((B, hq, T, d), jnp.float32))


def _finalize(acc, l):
    return acc / jnp.maximum(l, 1e-30)[..., None]


# ---------------------------------------------------------------------- #
# Pallas partial-block kernel vs the full flash kernel
# ---------------------------------------------------------------------- #

@pytest.mark.parametrize("T", [128, 200])   # 200: non-dividing block pad
def test_partial_kernel_single_block(T):
    q, k, v = _qkv_bhtd(T, T)
    full = ops.flash_attention(q, k, v, causal=True)
    m, l, acc = _partial_init(1, 4, T, 32)
    acc, m, l = ops.flash_attention_partial(q, k, v, m, l, acc,
                                            causal=True)
    err = float(jnp.max(jnp.abs(_finalize(acc, l) - full)))
    assert err < 1e-5, err


def test_partial_kernel_chained_blocks():
    T = 200
    q, k, v = _qkv_bhtd(T, T)
    full = ops.flash_attention(q, k, v, causal=True)
    m, l, acc = _partial_init(1, 4, T, 32)
    s1 = 72  # non-block-aligned split
    acc, m, l = ops.flash_attention_partial(
        q, k[:, :, :s1], v[:, :, :s1], m, l, acc, causal=True, k_pos0=0)
    acc, m, l = ops.flash_attention_partial(
        q, k[:, :, s1:], v[:, :, s1:], m, l, acc, causal=True, k_pos0=s1)
    err = float(jnp.max(jnp.abs(_finalize(acc, l) - full)))
    assert err < 1e-5, err


def test_partial_kernel_strided_positions():
    """Striped context-parallel positions: rank r of p=2 holds global
    positions r, r+2, r+4, ... — the kernel's affine (pos0, stride)
    masks must reproduce dense causal attention on the interleaving."""
    p, C = 2, 64
    T = p * C
    q, k, v = _qkv_bhtd(T, T)
    full = ops.flash_attention(q, k, v, causal=True)
    for r in range(p):
        qr = q[:, :, r::p]
        m, l, acc = _partial_init(1, 4, C, 32)
        for owner in range(p):
            acc, m, l = ops.flash_attention_partial(
                qr, k[:, :, owner::p], v[:, :, owner::p], m, l, acc,
                causal=True, q_pos0=r, q_stride=p, k_pos0=owner,
                k_stride=p)
        err = float(jnp.max(jnp.abs(_finalize(acc, l) - full[:, :, r::p])))
        assert err < 1e-5, (r, err)


def test_partial_oracle_matches_kernel_windowed():
    """The jnp oracle (attn_core_partial, layer layout) and the Pallas
    partial kernel agree on a sliding-window block with vector/affine
    positions respectively."""
    T, W = 96, 37
    q, k, v = _qkv_bhtd(T, T, d=32)
    m, l, acc = _partial_init(1, 4, T, 32)
    acc, m, l = ops.flash_attention_partial(q, k, v, m, l, acc,
                                            causal=True, window=W)
    out_kernel = _finalize(acc, l)
    # oracle works in (B, T, H, D)
    qt, kt, vt = (jnp.swapaxes(t, 1, 2) for t in (q, k, v))
    pos = jnp.arange(T)
    carry = A.attn_partial_init(1, T, 2, 2, 32)
    carry = A.attn_core_partial(qt, kt, vt, carry, q_pos=pos, k_pos=pos,
                                causal=True, window=W)
    out_oracle = A.attn_partial_finalize(carry, jnp.float32)
    err = float(jnp.max(jnp.abs(jnp.swapaxes(out_kernel, 1, 2)
                                - out_oracle)))
    assert err < 1e-5, err


# ---------------------------------------------------------------------- #
# striped layout helpers
# ---------------------------------------------------------------------- #

def test_stripe_roundtrip_and_layout():
    x = jnp.arange(2 * 12).reshape(2, 12)
    for p in (1, 2, 3, 4, 6):
        assert (M.unstripe_seq(M.stripe_seq(x, p), p) == x).all()
    s = np.asarray(M.stripe_seq(x, 4))
    xn = np.asarray(x)
    C = 12 // 4
    for r in range(4):
        for j in range(C):
            # contiguous shard r holds global positions r, r+p, r+2p, ...
            assert (s[:, r * C + j] == xn[:, j * 4 + r]).all()
    with pytest.raises(ValueError):
        M.stripe_seq(x, 5)


# ---------------------------------------------------------------------- #
# seq_attn parity under shard_map
# ---------------------------------------------------------------------- #

def _seq_mesh(p):
    return LM.make_smoke_mesh((1, 1, 1, 1, p),
                              ("data", "x", "y", "z", "seq"))


def test_seq_attn_gseq1_bitwise():
    """g_seq == 1 must degenerate to the plain core, bit for bit."""
    axes = LM.bind_4d(LM.make_smoke_mesh((1, 1, 2, 1)))
    q, k, v = _qkv_bthd(64)
    out = A.seq_attn(q, k, v, axes, causal=True)
    ref = A.attn_core(q, k, v, causal=True)
    assert (np.asarray(out) == np.asarray(ref)).all()


@pytest.mark.parametrize("p", [2, 4])
@pytest.mark.parametrize("ring", [False, True])
@pytest.mark.parametrize("window", [0, 37])
def test_seq_attn_parity(p, ring, window):
    if p > N_DEVICES:
        pytest.skip(f"needs {p} devices")
    mesh = _seq_mesh(p)
    axes = LM.bind_4d(mesh)
    if ring:
        axes = axes.with_overlap(OverlapConfig(ring_attention=True))
    q, k, v = _qkv_bthd(64)
    ref = A.attn_core(q, k, v, causal=True, window=window)
    qs, ks, vs = (M.stripe_seq(t, p) for t in (q, k, v))
    spec = P(None, "seq", None, None)
    f = shard_map(
        lambda a, b, c: A.seq_attn(a, b, c, axes, causal=True,
                                   window=window),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    out = M.unstripe_seq(f(qs, ks, vs), p)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-6, rtol=2e-6)


@pytest.mark.skipif(N_DEVICES < 4, reason="needs a 4-way seq axis")
def test_seq_attn_hlo_contract():
    """The ring schedule must lower the KV exchange to collective-permute
    chains; the full-sequence all-gather may only appear in blocking
    mode."""
    from repro.launch import roofline as RL
    p = 4
    mesh = _seq_mesh(p)
    q, k, v = _qkv_bthd(64)
    qs, ks, vs = (M.stripe_seq(t, p) for t in (q, k, v))
    spec = P(None, "seq", None, None)
    counts = {}
    for ring in (False, True):
        axes = LM.bind_4d(mesh).with_overlap(
            OverlapConfig(ring_attention=ring))
        f = jax.jit(shard_map(
            lambda a, b, c, ax=axes: A.seq_attn(a, b, c, ax, causal=True),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec))
        hlo = f.lower(qs, ks, vs).compile().as_text()
        counts[ring] = RL.parse_collectives(hlo).counts
    assert counts[False].get("all-gather", 0) > 0, counts
    assert counts[True].get("all-gather", 0) == 0, counts
    assert (counts[True].get("collective-permute", 0)
            >= 2 * (p - 1)), counts  # k and v rings, p-1 hops each


# ---------------------------------------------------------------------- #
# end-to-end: train-loss parity vs an unsharded decomposition
# ---------------------------------------------------------------------- #

def _train_losses(mesh_shape, steps=3, B=4, S=32):
    from repro.configs import get_config
    from repro.core.partition import spec_tree_to_pspecs
    from repro.launch import steps as ST
    from repro.optim.adamw import AdamWConfig, init_state

    names = ("data", "x", "y", "z", "seq")[:len(mesh_shape)]
    mesh = LM.make_smoke_mesh(mesh_shape, names)
    axes = LM.bind_4d(mesh)
    cfg = get_config("stablelm-1.6b").reduced()
    params, specs = ST.init_model(cfg, axes, jax.random.PRNGKey(0),
                                  dtype=jnp.float32)
    params = ST.device_put_tree(mesh, params, spec_tree_to_pspecs(specs))
    state = init_state(params)
    fn, _, _ = ST.make_train_step(
        cfg, mesh, axes,
        AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=50),
        ST.TrainOptions(dtype=jnp.float32))
    rng = np.random.RandomState(0)
    batch = {"tokens": jnp.asarray(rng.randint(0, cfg.vocab_size, (B, S)),
                                   jnp.int32),
             "labels": jnp.asarray(rng.randint(0, cfg.vocab_size, (B, S)),
                                   jnp.int32)}
    batch = ST.stripe_batch(batch, axes)
    losses = []
    for _ in range(steps):
        params, state, m = fn(params, state, batch)
        losses.append(float(m["loss"]))
    return losses


@pytest.mark.skipif(N_DEVICES < 4, reason="needs 4 devices")
def test_train_loss_parity_seq_vs_unsharded():
    """Same model/data on (y=2) vs (y=2, seq=2): the loss trajectories
    must coincide — this exercises the striped batch/positions, the
    token-axes loss reduction and the seq-axis gradient psum (a missing
    grad reduction diverges by step 2)."""
    base = _train_losses((1, 1, 2, 1))
    seq = _train_losses((1, 1, 2, 1, 2))
    gap = max(abs(a - b) for a, b in zip(base, seq))
    assert gap < 1e-3, (base, seq)


# ---------------------------------------------------------------------- #
# satellite: ring embedding gather (bitwise vs blocking AG_z)
# ---------------------------------------------------------------------- #

@pytest.mark.skipif(N_DEVICES < 8, reason="needs the z=2 mesh")
def test_embed_ring_gather_bitwise(meshz, axesz):
    V, H, B, S = 64, 32, 2, 16
    tokens = jnp.asarray(
        np.random.RandomState(0).randint(0, V, (B, S)), jnp.int32)
    table = jax.random.normal(jax.random.PRNGKey(1), (V, H))
    tspec = axesz.pspec(axesz.y, M._names(axesz.x) + M._names(axesz.z))
    outs = {}
    for ring in (False, True):
        axes = axesz.with_overlap(OverlapConfig(embed_gather=ring))
        f = shard_map(
            lambda t, w, ax=axes: PP.embedding_lookup(t, w, ax),
            mesh=meshz, in_specs=(P(None, None), tspec),
            out_specs=axesz.pspec(None, None, axesz.x),
            check_vma=False)  # custom-vjp lookup defeats the rep checker
        outs[ring] = np.asarray(f(tokens, table))
    assert (outs[False] == outs[True]).all()


# ---------------------------------------------------------------------- #
# satellite: softmax accumulates in fp32 regardless of activation dtype
# ---------------------------------------------------------------------- #

def test_attn_core_softmax_fp32_under_bf16():
    q, k, v = _qkv_bthd(64)
    qb, kb, vb = (t.astype(jnp.bfloat16) for t in (q, k, v))
    # fp32 math on the same rounded inputs: the bf16 path may differ only
    # by the final output-dtype cast (scores/softmax/PV all in fp32)
    ref = A.attn_core(qb.astype(jnp.float32), kb.astype(jnp.float32),
                      vb.astype(jnp.float32), causal=True)
    out = A.attn_core(qb, kb, vb, causal=True)
    assert out.dtype == jnp.bfloat16
    assert (np.asarray(out) == np.asarray(ref.astype(jnp.bfloat16))).all()
    # chunked (online-softmax) path: fp32 carries, tolerance-level parity
    out_c = A.attn_core(qb, kb, vb, causal=True, chunked_threshold=16)
    err = float(jnp.max(jnp.abs(out_c.astype(jnp.float32) - ref)))
    assert err < 8e-3, err  # one bf16 output rounding, not a bf16 softmax


# ---------------------------------------------------------------------- #
# comm model: the ring_exchange collective class
# ---------------------------------------------------------------------- #

def test_comm_model_gseq1_degenerate():
    from repro.configs import get_config
    from repro.core import comm_model as CM
    layers = list(get_config("stablelm-1.6b").reduced().comm_layers())
    d4 = CM.Decomposition(2, 2, 2, 1)
    d5 = CM.Decomposition(2, 2, 2, 1, 1)
    assert CM.model_volume(layers, 4096, d4) == \
        CM.model_volume(layers, 4096, d5)
    assert CM.predict_step_time(layers, 4096, d4).total == \
        CM.predict_step_time(layers, 4096, d5).total


def test_comm_model_ring_exchange_pricing():
    from repro.core import comm_model as CM
    assert CM.ring_exchange_volume(1, 10.0) == 0.0
    assert CM.ring_exchange_volume(4, 10.0) == 30.0  # (p-1) full blocks
    hw = dataclasses.replace(CM.TPU_V5E, alpha=0.0, gamma=0.0)
    t = CM.collective_time("ring_exchange", 4, 10.0, hw)
    assert t == pytest.approx(30.0 * hw.bytes_per_elem / hw.link_bw)
    assert CM.collective_time("ring_exchange", 1, 10.0, hw) == 0.0
    # α charges one hop per ring step: p-1 of them
    hw_a = dataclasses.replace(CM.TPU_V5E, gamma=0.0)
    assert CM.collective_time("ring_exchange", 4, 10.0, hw_a) == \
        pytest.approx(t + 3 * hw_a.alpha)


def test_enumerate_decompositions_seq():
    from repro.core import comm_model as CM
    base = list(CM.enumerate_decompositions(16))
    assert all(d.g_seq == 1 for d in base)  # default stays 4-factor
    cons = CM.Constraints(max_seq=4, seq_divides=(128,))
    ds = list(CM.enumerate_decompositions(16, cons))
    assert {d.g_seq for d in ds} == {1, 2, 4}
    assert all(math.prod((d.g_data, d.g_x, d.g_y, d.g_z, d.g_seq)) == 16
               for d in ds)
    # g_seq stays out of the weight-sharding product
    d = next(d for d in ds if d.g_seq == 4)
    assert d.g_tensor == d.g_x * d.g_y * d.g_z
