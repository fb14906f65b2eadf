"""Core 4D tensor-parallel primitives vs single-device dense reference:
forward values AND gradients must match exactly (the paper's Fig. 6
statistical-efficiency claim, in unit-test form)."""
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
from repro.launch import mesh as LM

K, N, B, S = 16, 24, 8, 8


@pytest.fixture(scope="module")
def data():
    key = jax.random.PRNGKey(0)
    kx, kw, kw2, kt = jax.random.split(key, 4)
    return {
        "x": jax.random.normal(kx, (B, S, K)),
        "w": jax.random.normal(kw, (K, N)) * 0.1,
        "w2": jax.random.normal(kw2, (N, K)) * 0.1,
        "gamma": jnp.ones((K,)),
        "labels": jax.random.randint(kt, (B, S), 0, N),
    }


def _ref(data):
    def loss(w, w2, gamma, x, labels):
        xf = x.astype(jnp.float32)
        ms = jnp.mean(xf * xf, -1, keepdims=True)
        h = xf * jax.lax.rsqrt(ms + 1e-6) * gamma
        y = h @ w
        y2 = jax.nn.gelu(y) @ w2
        logits = (y2 @ w).astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, -1)
        tgt = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
        return jnp.mean(lse - tgt)
    val, grads = jax.value_and_grad(loss, argnums=(0, 1, 2))(
        data["w"], data["w2"], data["gamma"], data["x"], data["labels"])
    return val, grads


MESHES = [
    ((2, 2, 2, 1), ("data", "x", "y", "z"),
     dict(data=("data",), x="x", y="y", z="z")),
    ((1, 2, 2, 2), ("data", "x", "y", "z"),
     dict(data=("data",), x="x", y="y", z="z")),
    ((2, 2, 1, 2), ("data", "x", "y", "z"),
     dict(data=("data",), x="x", y="y", z="z")),
    ((1, 2, 2, 1), ("data", "x", "y", "z"),
     dict(data=("data",), x="x", y="y", z="z")),
    ((1, 1, 2, 2), ("data", "x", "y", "z"),
     dict(data=("data",), x="x", y="y", z="z")),
    ((2, 4), ("data", "model"), dict(data=("data",), x="model")),
    ((4, 2), ("data", "model"), dict(data=("data",), y="model")),
    ((2, 2), ("data", "model"), dict(data=("data",), x="model")),
    ((2, 2, 2), ("pod", "data", "model"),
     dict(data=("pod", "data"), y="model")),
    ((2, 2, 1), ("pod", "data", "model"),
     dict(data=("pod", "data"), x="model")),
]
MESHES = [m for m in MESHES if math.prod(m[0]) <= N_DEVICES]


@pytest.mark.parametrize("shape,names,bind", MESHES,
                         ids=[str(m[0]) + str(m[2].get("x")) for m in MESHES])
def test_tp_matches_dense(shape, names, bind, data):
    mesh = LM.make_smoke_mesh(shape, names)
    axes = M.bind_axes(mesh, **bind)
    ref_val, ref_grads = _ref(data)

    wspec = PP.yz_spec(axes, False)
    w2spec = PP.yz_spec(axes, True)
    gspec = axes.pspec(axes.x)
    bax = axes.batch_axes()
    xspec = axes.pspec(bax, None, axes.x)
    lspec = axes.pspec(bax, None)

    def par_loss(w, w2, gamma, x, labels):
        h = PP.rms_norm(x, gamma, axes, K)
        y = PP.tp_matmul(h, w, axes, "x", "y")
        y2 = PP.tp_matmul(jax.nn.gelu(y), w2, axes, "y", "x")
        logits = PP.tp_matmul(y2, w, axes, "x", "y")
        tot = PP.ar_bwd_identity(
            jnp.sum(PP.vocab_parallel_xent(logits, labels, axes)),
            axes.batch_axes())
        return tot / (B * S)

    def step(w, w2, gamma, x, labels):
        val, grads = jax.value_and_grad(par_loss, argnums=(0, 1, 2))(
            w, w2, gamma, x, labels)
        gw, gw2, gg = grads
        gw = M.psum(gw, axes.data)
        gw2 = M.psum(gw2, axes.data)
        gg = M.psum(M.psum(gg, axes.data), axes.z)
        return val, (gw, gw2, gg)

    f = shard_map(step, mesh=mesh,
                  in_specs=(wspec, w2spec, gspec, xspec, lspec),
                  out_specs=(P(), (wspec, w2spec, gspec)), check_vma=False)
    val, grads = jax.jit(f)(data["w"], data["w2"], data["gamma"], data["x"],
                            data["labels"])
    np.testing.assert_allclose(np.asarray(val), np.asarray(ref_val),
                               rtol=1e-5)
    for g, rg in zip(grads, ref_grads):
        np.testing.assert_allclose(np.asarray(g), np.asarray(rg),
                                   rtol=3e-4, atol=1e-5)


def test_embedding_and_tied_head(mesh4, axes4):
    V, H = 32, 16
    key = jax.random.PRNGKey(1)
    table = jax.random.normal(key, (V, H)) * 0.1
    toks = jax.random.randint(jax.random.PRNGKey(2), (4, 8), 0, V)

    def ref(table):
        h = table[toks]
        logits = h @ table.T
        return jnp.sum(logits ** 2)

    rv, rg = jax.value_and_grad(ref)(table)

    tspec = axes4.pspec(axes4.y, M._names(axes4.x) + M._names(axes4.z))

    def par(table, toks):
        h = PP.embedding_lookup(toks, table, axes4)
        logits = PP.tied_lm_logits(h, table, axes4)
        # logits (B,T,V/y) replicated over x; sum of squares over full V
        # (ar_bwd_identity: raw psum autodiff would double the cotangent)
        loc = jnp.sum(logits.astype(jnp.float32) ** 2)
        return PP.ar_bwd_identity(loc, axes4.y)

    def step(table, toks):
        v, g = jax.value_and_grad(par)(table, toks)
        return v, g

    f = shard_map(step, mesh=mesh4, in_specs=(tspec, P(None, None)),
                  out_specs=(P(), tspec), check_vma=False)
    v, g = jax.jit(f)(table, toks)
    np.testing.assert_allclose(np.asarray(v), np.asarray(rv), rtol=1e-4)
    np.testing.assert_allclose(np.asarray(g), np.asarray(rg), rtol=1e-3,
                               atol=1e-5)


def test_layer_norm_matches(mesh4, axes4):
    D = 16
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 8, D))
    g0 = jnp.ones((D,)) * 1.3
    b0 = jnp.ones((D,)) * 0.1

    def ref(x, g, b):
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.var(x, -1, keepdims=True)
        return jnp.sum((x - mu) * jax.lax.rsqrt(var + 1e-5) * g + b)

    rv, rgs = jax.value_and_grad(ref, argnums=(0, 1, 2))(x, g0, b0)

    gspec = axes4.pspec(axes4.x)
    xspec = axes4.pspec(axes4.data, None, axes4.x)

    sum_axes = M._names(axes4.data) + M._names(axes4.x)

    def par(x, g, b):
        y = PP.layer_norm(x, g, b, axes4, D)
        return PP.ar_bwd_identity(jnp.sum(y.astype(jnp.float32)), sum_axes)

    def step(x, g, b):
        v, grads = jax.value_and_grad(par, argnums=(0, 1, 2))(x, g, b)
        gx, gg, gb = grads
        return v, (gx, M.psum(gg, axes4.data), M.psum(gb, axes4.data))

    f = shard_map(step, mesh=mesh4, in_specs=(xspec, gspec, gspec),
                  out_specs=(P(), (xspec, gspec, gspec)), check_vma=False)
    v, (gx, gg, gb) = jax.jit(f)(x, g0, b0)
    np.testing.assert_allclose(np.asarray(v), np.asarray(rv), rtol=1e-4)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(rgs[0]),
                               rtol=1e-3, atol=1e-5)
    np.testing.assert_allclose(np.asarray(gg), np.asarray(rgs[1]),
                               rtol=1e-3, atol=1e-5)
    np.testing.assert_allclose(np.asarray(gb), np.asarray(rgs[2]),
                               rtol=1e-3, atol=1e-5)
