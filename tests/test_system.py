"""End-to-end behaviour tests for the whole system: real multi-step
training runs that must converge, checkpoint/restore continuity, and
decomposition-invariance of the training trajectory (paper Fig. 6)."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from conftest import N_DEVICES
from repro.configs import get_config
from repro.core.partition import spec_tree_to_pspecs
from repro.data.synthetic import DataConfig, SyntheticText, make_batch
from repro.launch import mesh as LM
from repro.launch import steps as ST
from repro.optim.adamw import AdamWConfig, init_state

# the default (2,2,2,1) smoke mesh, shrunk to fit 4-device CI hosts
SHAPE0 = (2, 2, 2, 1) if N_DEVICES >= 8 else (1, 2, 2, 1)
# three decompositions of the same device count (trajectory invariance)
SHAPES_INV = ([(2, 2, 2, 1), (2, 1, 4, 1), (1, 2, 2, 2)]
              if N_DEVICES >= 8
              else [(1, 2, 2, 1), (2, 1, 2, 1), (1, 1, 2, 2)])


def test_init_sharded_matches_init_model(mesh4, axes4):
    """``init_sharded`` computes ``init_model``'s values straight into the
    parameters' shardings. Under one jit XLA may fold an init scale
    factor differently from the eager ops: a last-bit difference (1 fp32
    ulp was observed), hence the tolerance."""
    cfg = get_config("qwen3-1.7b").reduced()
    key = jax.random.PRNGKey(3)
    ref, specs = ST.init_model(cfg, axes4, key, dtype=jnp.float32)
    got, _ = ST.init_sharded(cfg, mesh4, axes4, key, dtype=jnp.float32)
    pspecs = spec_tree_to_pspecs(specs)
    for r, g, s in zip(jax.tree.leaves(ref), jax.tree.leaves(got),
                       jax.tree.leaves(pspecs,
                                       is_leaf=lambda x: isinstance(x, P))):
        assert g.sharding.mesh == mesh4 and g.sharding.spec == s
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), rtol=1e-6,
                                   atol=0)


def _run(arch, mesh_shape, steps, *, seed=0, B=8, S=64, od=2):
    mesh = LM.make_smoke_mesh(mesh_shape)
    axes = LM.bind_4d(mesh)
    cfg = get_config(arch).reduced()
    params, specs = ST.init_model(cfg, axes, jax.random.PRNGKey(seed),
                                  dtype=jnp.float32)
    params = ST.device_put_tree(mesh, params, spec_tree_to_pspecs(specs))
    state = init_state(params)
    fn, _, _ = ST.make_train_step(
        cfg, mesh, axes, AdamWConfig(lr=1e-3, warmup_steps=2,
                                     total_steps=steps),
        ST.TrainOptions(overdecompose=od, dtype=jnp.float32))
    data = SyntheticText(DataConfig(cfg.vocab_size, S, B, seed=1))
    losses = []
    for step in range(steps):
        batch = {k: jnp.asarray(v)
                 for k, v in make_batch(cfg, step, data).items()}
        params, state, m = fn(params, state, batch)
        losses.append(float(m["loss"]))
    return cfg, params, losses


def test_training_converges_markov():
    """The markov synthetic task is learnable: loss must drop well below
    the starting entropy within 25 steps."""
    _, _, losses = _run("stablelm-1.6b", SHAPE0, 25)
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0] * 0.75, losses[::6]


def test_trajectory_invariant_to_decomposition():
    """Paper Fig. 6: the training trajectory must not depend on the
    decomposition (same init, same data, different meshes)."""
    _, _, l1 = _run("qwen3-1.7b", SHAPES_INV[0], 4)
    _, _, l2 = _run("qwen3-1.7b", SHAPES_INV[1], 4)
    _, _, l3 = _run("qwen3-1.7b", SHAPES_INV[2], 4)
    np.testing.assert_allclose(l1, l2, rtol=2e-4)
    np.testing.assert_allclose(l1, l3, rtol=2e-4)


def test_checkpoint_resume_continues(tmp_path):
    from repro.checkpoint import restore, save
    cfg, params, losses = _run("stablelm-1.6b", SHAPE0, 3)
    host = jax.tree.map(np.asarray, params)
    path = os.path.join(tmp_path, "ck.npz")
    save(path, host, step=3)
    got, step = restore(path, host)
    assert step == 3
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(host)):
        np.testing.assert_array_equal(a, b)


def test_prefill_then_decode_consistent():
    """Prefill+decode must give the same next-token logits as running the
    full sequence through the train-mode forward."""
    from repro.models import decoder as D
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    mesh = LM.make_smoke_mesh(SHAPE0)
    axes = LM.bind_4d(mesh)
    cfg = get_config("qwen3-1.7b").reduced()
    params, specs = ST.init_model(cfg, axes, jax.random.PRNGKey(0),
                                  dtype=jnp.float32)
    pspecs = spec_tree_to_pspecs(specs)
    params = ST.device_put_tree(mesh, params, pspecs)
    rng = np.random.RandomState(0)
    toks = jnp.asarray(rng.randint(0, cfg.vocab_size, (2, 9)), jnp.int32)

    # full forward logits at position 8
    def full(params, toks):
        h, _, _ = D.decoder_hidden(params, cfg, axes, toks, mode="train",
                                   remat=False)
        return D.lm_logits(params, cfg, axes, h[:, -1:, :])

    f = shard_map(full, mesh=mesh,
                  in_specs=(pspecs, axes.pspec(axes.batch_axes(), None)),
                  out_specs=axes.pspec(axes.batch_axes(), None, axes.y),
                  check_vma=False)
    want = np.asarray(jax.jit(f)(params, toks))

    # prefill on the first 8 tokens, then decode token 8
    pre_build, _ = ST.make_prefill_step(cfg, mesh, axes, dtype=jnp.float32)
    pre_fn, bt, ct = pre_build(2, 8, 16)
    caches = ST.zeros_caches(mesh, ct)
    _, caches = pre_fn(params, caches, {"tokens": toks[:, :8]})
    dec_build, _ = ST.make_decode_step(cfg, mesh, axes, dtype=jnp.float32)
    dec_fn, _ = dec_build(2, 16)
    got, _ = dec_fn(params, caches, toks[:, 8:9], jnp.int32(8))
    np.testing.assert_allclose(np.asarray(got)[:, 0], want[:, 0],
                               rtol=2e-3, atol=2e-4)
