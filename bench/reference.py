"""The plain reference: the configuration's model in straightforward
``jax.numpy`` and float32 at the highest matmul precision, its loss and
AdamW. It imports nothing of the program and takes nothing the program
made: its weights come from ``bench.weights.canonical`` and the seed.

The model and its loss are the family's (``bench/families/<family>.py``,
``loss_mean``); what is here serves every family: the products of each
mode, and three AdamW steps with the readings the comparison takes.

``mode="fp8"`` computes every matrix product on operands rounded to
float8 e4m3 with a per-tensor scale, and rounds the gradients flowing
back into them the same way: the control of the comparisons.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from bench import weights as W
from bench.common import base_key, family

HIGHEST = jax.lax.Precision.HIGHEST


def _fp8(x):
    amax = jnp.max(jnp.abs(x))
    s = jnp.where(amax > 0, 448.0 / amax, 1.0)
    return (x * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s


@jax.custom_vjp
def q8(x):
    return _fp8(x)


q8.defvjp(lambda x: (_fp8(x), None), lambda _, g: (_fp8(g),))


def einsum(mode: str):
    """``einsum(eq, a, b)`` of ``mode``: float32 at the highest precision,
    or on operands rounded to float8 (the control)."""
    if mode == "fp32":
        return lambda eq, a, b: jnp.einsum(eq, a, b, precision=HIGHEST)
    if mode == "fp8":
        return lambda eq, a, b: jnp.einsum(eq, q8(a), q8(b),
                                           precision=HIGHEST)
    raise ValueError(mode)


# ---------------------------------------------------------------------- #
# training: three AdamW steps
# ---------------------------------------------------------------------- #

def lr_at(opt: dict, step: int) -> float:
    """Linear warmup, then cosine decay to ``min_lr_ratio`` of the peak;
    step 0 takes learning rate 0."""
    warm = min(step / max(opt["warmup_steps"], 1), 1.0)
    prog = min(max((step - opt["warmup_steps"])
                   / max(opt["total_steps"] - opt["warmup_steps"], 1), 0.0),
               1.0)
    cos = 0.5 * (1 + math.cos(math.pi * prog))
    return opt["lr"] * warm * (opt["min_lr_ratio"]
                               + (1 - opt["min_lr_ratio"]) * cos)


def decays(name: str) -> bool:
    """Weight decay on matrices, not on norm gains or biases."""
    return not (name.endswith(".g") or name.endswith(".b"))


def slice_norms(tree: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
    """L2 norm of each leaf, per layer for stacked ``layers.*`` leaves."""
    out = {}
    for k, x in tree.items():
        x = x.astype(jnp.float32)
        if k.startswith("layers."):
            out[k] = jnp.sqrt(jnp.sum(jnp.square(x),
                                      axis=tuple(range(1, x.ndim))))
        else:
            out[k] = jnp.sqrt(jnp.sum(jnp.square(x)))[None]
    return out


def change_norms(w, w0):
    """Per-leaf norms of the change from ``w0`` (see :func:`slice_norms`)."""
    return slice_norms({k: x - w0[k].astype(jnp.float32)
                        for k, x in w.items()})


def train_readings(c: dict, opt: dict, seed: int, batches, *,
                   mode: str = "fp32", shardings=None) -> dict:
    """Follow the first ``len(batches)`` steps from the seed's weights.

    Returns host numpy readings: ``loss`` per step, ``grad`` (per-leaf
    norms of the first gradient as AdamW gets it, after clipping) and
    ``change`` (per-leaf norms of the weights' change after the steps).
    ``batches``: list of (tokens, labels) device arrays.
    """
    import numpy as np
    key = base_key(seed)
    jit_kw = {} if shardings is None else {"out_shardings": shardings}
    make = jax.jit(lambda k: {n: v.astype(jnp.float32) for n, v in
                              W.canonical(c, k).items()}, **jit_kw)
    w = make(key)
    loss_mean = family(c).loss_mean
    vg = jax.jit(jax.value_and_grad(
        lambda w, t, l: loss_mean(w, t, l, c, mode)))

    gnorm = jax.jit(lambda g: jnp.sqrt(sum(jnp.sum(jnp.square(x))
                                           for x in g.values())))
    scaled_norms = jax.jit(lambda g, s: {k: v * s for k, v in
                                         slice_norms(g).items()})

    def update(w, m, v, g, s, t, lr):
        b1, b2 = opt["b1"], opt["b2"]
        nw, nm, nv = {}, {}, {}
        for k in w:
            gk = g[k] * s
            nm[k] = b1 * m[k] + (1 - b1) * gk
            nv[k] = b2 * v[k] + (1 - b2) * gk * gk
            upd = (nm[k] / (1 - b1 ** t)) / (
                jnp.sqrt(nv[k] / (1 - b2 ** t)) + opt["eps"])
            if decays(k):
                upd = upd + opt["weight_decay"] * w[k]
            nw[k] = w[k] - lr * upd
        return nw, nm, nv
    update = jax.jit(update, donate_argnums=(0, 1, 2))

    m = jax.tree.map(jnp.zeros_like, w)
    v = jax.tree.map(jnp.zeros_like, w)
    losses, grad = [], None
    for t, (tok, lab) in enumerate(batches):
        loss, g = vg(w, tok, lab)
        # clipped as AdamW clips: by the global norm, to grad_clip
        s = min(1.0, opt["grad_clip"] / (float(gnorm(g)) + 1e-12))
        if t == 0:
            grad = jax.device_get(scaled_norms(g, s))
        losses.append(float(loss))
        w, m, v = update(w, m, v, g, s, float(t + 1), lr_at(opt, t))
        del g
    del m, v
    change = jax.jit(lambda w, k: change_norms(w, W.canonical(c, k)))(w, key)
    change = jax.device_get(change)
    del w
    return {"loss": np.asarray(losses),
            "grad": {k: np.asarray(x) for k, x in grad.items()},
            "change": {k: np.asarray(x) for k, x in change.items()}}
