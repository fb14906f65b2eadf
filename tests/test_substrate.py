"""Substrate tests: optimizer, checkpointing, MoE dispatch, and
overdecomposition equivalence."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.core import mesh as M
from repro.core import parallel as PP


# --------------------------------------------------------------------- #
# optimizer
# --------------------------------------------------------------------- #

def test_adamw_converges_quadratic():
    from repro.core.mesh import MeshAxes
    from repro.core.partition import Boxed, unbox
    from repro.optim.adamw import AdamWConfig, apply_updates, init_state

    axes = MeshAxes(data=(), x=None, y=None, z=None, sizes=())
    target = jnp.arange(8.0)
    boxed = {"w": Boxed(jnp.zeros(8), P())}
    params, specs = unbox(boxed)
    state = init_state(params)
    cfg = AdamWConfig(lr=0.2, weight_decay=0.0, warmup_steps=1,
                      total_steps=200, grad_clip=0)

    @jax.jit
    def step(params, state):
        g = jax.grad(lambda p: jnp.sum((p["w"] - target) ** 2))(params)
        return apply_updates(params, g, state, specs, axes, cfg)

    for _ in range(150):
        params, state, _ = step(params, state)
    np.testing.assert_allclose(np.asarray(params["w"]), np.asarray(target),
                               atol=0.5)


def test_grad_clip_scales():
    from repro.core.partition import Boxed, unbox
    from repro.optim.adamw import AdamWConfig, apply_updates, init_state
    from repro.core.mesh import MeshAxes

    axes = MeshAxes(data=(), x=None, y=None, z=None, sizes=())
    boxed = {"w": Boxed(jnp.zeros(4), P())}
    params, specs = unbox(boxed)
    state = init_state(params)
    big = {"w": jnp.full(4, 100.0)}
    cfg = AdamWConfig(grad_clip=1.0, warmup_steps=0, total_steps=10)
    _, _, m = apply_updates(params, big, state, specs, axes, cfg)
    assert float(m["grad_norm"]) == pytest.approx(200.0)


# --------------------------------------------------------------------- #
# checkpoint
# --------------------------------------------------------------------- #

def test_checkpoint_roundtrip(tmp_path):
    from repro.checkpoint import restore, save
    tree = {"a": {"w": jnp.arange(6.0).reshape(2, 3)},
            "b": jnp.ones(4, jnp.int32)}
    path = os.path.join(tmp_path, "ck.npz")
    save(path, tree, step=17)
    got, step = restore(path, tree)
    assert step == 17
    np.testing.assert_array_equal(np.asarray(got["a"]["w"]),
                                  np.asarray(tree["a"]["w"]))
    np.testing.assert_array_equal(np.asarray(got["b"]),
                                  np.asarray(tree["b"]))


def test_checkpoint_shape_mismatch_raises(tmp_path):
    from repro.checkpoint import restore, save
    path = os.path.join(tmp_path, "ck.npz")
    save(path, {"w": jnp.ones((2, 2))})
    with pytest.raises(ValueError):
        restore(path, {"w": jnp.ones((3, 2))})


# --------------------------------------------------------------------- #
# MoE: capacity-dispatch conservation vs dense loop oracle
# --------------------------------------------------------------------- #

def test_moe_matches_dense_loop(mesh4, axes4):
    from repro.configs import get_config
    from repro.core.partition import unbox
    from repro.layers import moe as MOE
    import dataclasses

    cfg = get_config("deepseek-v2-lite-16b").reduced()
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0,
                                     n_shared=0))  # no drops, no shared
    key = jax.random.PRNGKey(0)
    boxed = MOE.moe_init(key, cfg, axes4, dtype=jnp.float32)
    params, specs = unbox(boxed)
    B, T = 2, 16
    h = jax.random.normal(jax.random.PRNGKey(1), (B, T, cfg.d_model)) * 0.3

    # dense oracle on unsharded params
    def oracle(params, h):
        hf = h.reshape(-1, cfg.d_model)
        logits = hf @ params["w_router"]
        gates, idx = MOE._topk_gates(logits.astype(jnp.float32), cfg.moe)
        out = jnp.zeros_like(hf)
        for e in range(cfg.moe.n_experts):
            w_up = params["w_up"][e]
            w_dn = params["w_down"][e]
            u = hf @ w_up
            g, u2 = jnp.split(u, 2, axis=-1)
            eo = (jax.nn.silu(g) * u2) @ w_dn
            for slot in range(cfg.moe.top_k):
                sel = (idx[:, slot] == e).astype(h.dtype)
                out = out + eo * (gates[:, slot] * sel)[:, None]
        return out.reshape(B, T, cfg.d_model)

    want = oracle(params, h)

    from repro.core.partition import spec_tree_to_pspecs
    pspecs = spec_tree_to_pspecs(specs)
    hspec = axes4.pspec(axes4.batch_axes(), None, axes4.x)

    f = shard_map(lambda p, h: MOE.moe_apply(p, h, cfg, axes4)[0],
                  mesh=mesh4, in_specs=(pspecs, hspec), out_specs=hspec,
                  check_vma=False)
    got = jax.jit(f)(params, h)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=5e-3, atol=5e-4)


# --------------------------------------------------------------------- #
# overdecomposition (paper §4.2): grads identical to full-batch
# --------------------------------------------------------------------- #

def test_overdecomposition_preserves_gradients():
    from repro.core.overdecompose import overdecomposed_value_and_grad

    w0 = jnp.array([1.0, -2.0, 0.5])
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 3))
    y = jax.random.normal(jax.random.PRNGKey(1), (8,))

    def loss(w, batch):
        pred = batch["x"] @ w
        return jnp.mean((pred - batch["y"]) ** 2)

    v1, g1 = jax.value_and_grad(loss)(w0, {"x": x, "y": y})
    v2, g2 = overdecomposed_value_and_grad(loss, 2)(w0, {"x": x, "y": y})
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), rtol=1e-5)


def test_overdecomposed_trainstep_matches(mesh4, axes4):
    """Full train step: overdecompose=2 equals overdecompose=1 (same data)."""
    from conftest import train_smoke
    _, l1 = train_smoke("stablelm-1.6b", mesh4, axes4, steps=2,
                        overdecompose=1, check_decreases=False)
    _, l2 = train_smoke("stablelm-1.6b", mesh4, axes4, steps=2,
                        overdecompose=2, check_decreases=False)
    np.testing.assert_allclose(l1, l2, rtol=1e-4)
