"""Channel-parallel U-Net (the paper's own model family): DDPM training
smoke + decomposition invariance of the loss."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from conftest import N_DEVICES
from repro.core import mesh as M
from repro.core.partition import spec_tree_to_pspecs, unbox, z_reduce_grads
from repro.launch import mesh as LM
from repro.models import unet as U

SHAPE0 = (2, 2, 2, 1) if N_DEVICES >= 8 else (1, 2, 2, 1)
SHAPES_INV = ([(2, 2, 2, 1), (2, 1, 4, 1), (1, 2, 2, 2)]
              if N_DEVICES >= 8
              else [(1, 2, 2, 1), (2, 1, 2, 1), (1, 1, 2, 2)])


def _run(mesh_shape, steps=3):
    mesh = LM.make_smoke_mesh(mesh_shape)
    axes = LM.bind_4d(mesh)
    cfg = U.UNetConfig().reduced()
    boxed = U.unet_init(jax.random.PRNGKey(0), cfg, axes,
                        dtype=jnp.float32)
    params, specs = unbox(boxed)
    pspecs = spec_tree_to_pspecs(specs)
    rng = np.random.RandomState(0)
    B = 8
    imgs = jnp.asarray(rng.randn(B, cfg.image_size, cfg.image_size, 3),
                       jnp.float32)
    t = jnp.asarray(rng.randint(0, 1000, (B,)), jnp.int32)
    noise = jnp.asarray(rng.randn(B, cfg.image_size, cfg.image_size, 3),
                        jnp.float32)
    bspec = axes.pspec(axes.batch_axes(), None, None, None)
    tspec = axes.pspec(axes.batch_axes())

    def sgd(params, imgs, t, noise):
        loss, grads = jax.value_and_grad(
            lambda p: U.ddpm_loss(p, cfg, axes, imgs, t, noise))(params)
        grads = jax.tree.map(lambda g: M.psum(g, axes.data), grads)
        grads = z_reduce_grads(grads, specs, axes, M.psum)
        new = jax.tree.map(lambda p, g: p - 1e-3 * g, params, grads)
        return new, loss

    fn = jax.jit(shard_map(sgd, mesh=mesh,
                           in_specs=(pspecs, bspec, tspec, bspec),
                           out_specs=(pspecs, P()), check_vma=False))
    losses = []
    for _ in range(steps):
        params, l = fn(params, imgs, t, noise)
        losses.append(float(l))
    return losses


def test_unet_ddpm_trains():
    losses = _run(SHAPE0)
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]


def test_unet_mesh_invariant():
    l1 = _run(SHAPES_INV[0], steps=2)
    l2 = _run(SHAPES_INV[1], steps=2)
    l3 = _run(SHAPES_INV[2], steps=2)
    np.testing.assert_allclose(l1, l2, rtol=2e-4)
    np.testing.assert_allclose(l1, l3, rtol=2e-4)
