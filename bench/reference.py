"""The plain reference: a dense decoder in straightforward ``jax.numpy``
and float32 at the highest matmul precision, its cross-entropy loss and
AdamW. It imports nothing of the program and takes nothing the program
made: its weights come from ``bench.weights.canonical`` and the seed.

It follows the published descriptions (LayerNorm or RMSNorm, partial
rotary embeddings, causal multi-head or grouped-query attention, gated
or plain MLP, untied head) with one departure that the program shares:
the rotary pairs are interleaved (dimensions 2i and 2i+1) where the
Hugging Face models rotate halves. The two are the same model up to a
fixed permutation of the query and key columns.

``mode="fp8"`` computes every matrix product on operands rounded to
float8 e4m3 with a per-tensor scale, and rounds the gradients flowing
back into them the same way: the control of the comparisons.

Memory: the layers run under a scan with rematerialization, attention
one row of the batch at a time, and the head and loss one row at a time,
so the reference fits beside nothing else on the program's chips.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from bench import weights as W
from bench.common import base_key

HIGHEST = jax.lax.Precision.HIGHEST


def _fp8(x):
    amax = jnp.max(jnp.abs(x))
    s = jnp.where(amax > 0, 448.0 / amax, 1.0)
    return (x * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s


@jax.custom_vjp
def q8(x):
    return _fp8(x)


q8.defvjp(lambda x: (_fp8(x), None), lambda _, g: (_fp8(g),))


def _einsum(mode: str):
    if mode == "fp32":
        return lambda eq, a, b: jnp.einsum(eq, a, b, precision=HIGHEST)
    if mode == "fp8":
        return lambda eq, a, b: jnp.einsum(eq, q8(a), q8(b),
                                           precision=HIGHEST)
    raise ValueError(mode)


def _norm(x, g, b, z):
    if z["norm"] == "layernorm":
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + z["eps"]) * g + b
    ms = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x * jax.lax.rsqrt(ms + z["eps"]) * g


def _act(name: str, x):
    if name == "silu":
        return jax.nn.silu(x)
    if name in ("gelu_new", "gelu_pytorch_tanh"):
        return jax.nn.gelu(x, approximate=True)
    if name == "gelu":
        return jax.nn.gelu(x, approximate=False)
    raise ValueError(name)


def _rope(x, z):
    """x (T, heads, hd): rotate the first ``partial_rotary_factor`` of
    each head, pairs (2i, 2i+1), angle position * theta^(-2i/rot)."""
    hd = x.shape[-1]
    rot = int(hd * z["rot"])
    rot -= rot % 2
    if rot == 0:
        return x
    inv = 1.0 / (z["theta"] ** (jnp.arange(0, rot, 2, dtype=jnp.float32)
                                / rot))
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0:rot:2], x[..., 1:rot:2]
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return jnp.concatenate([out.reshape(*x.shape[:-1], rot), x[..., rot:]],
                           -1)


def _attention(q, k, v, z, es):
    """One sequence: q (T, H, hd), k/v (T, KV, hd); causal softmax."""
    T, H, hd = q.shape
    g = H // z["KV"]
    qg = q.reshape(T, z["KV"], g, hd)
    s = es("qhgd,khd->hgqk", qg, k) / math.sqrt(hd)
    mask = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = es("hgqk,khd->qhgd", p, v)
    return o.reshape(T, H * hd)


def hidden(w: Dict[str, jax.Array], tokens, c: dict, mode: str = "fp32"):
    """tokens (B, T) int -> final-normed hidden states (B, T, d)."""
    z = W.dims(c)
    es = _einsum(mode)
    B, T = tokens.shape
    h = w["embed"][tokens]
    layer_keys = sorted(k for k in w if k.startswith("layers."))

    def get(lw, name):
        return lw.get("layers." + name)

    @jax.checkpoint
    def row_attn(q, k, v):
        return _attention(q, k, v, z, es)

    def body(h, lw):
        x = _norm(h, get(lw, "norm1.g"), get(lw, "norm1.b"), z)
        q = es("btd,dn->btn", x, lw["layers.wq"]).reshape(B, T, z["H"],
                                                          z["hd"])
        k = es("btd,dn->btn", x, lw["layers.wk"]).reshape(B, T, z["KV"],
                                                          z["hd"])
        v = es("btd,dn->btn", x, lw["layers.wv"]).reshape(B, T, z["KV"],
                                                          z["hd"])
        q = jax.vmap(lambda a: _rope(a, z))(q)
        k = jax.vmap(lambda a: _rope(a, z))(k)
        o = jax.lax.map(lambda a: row_attn(*a), (q, k, v))
        h = h + es("btn,nd->btd", o, lw["layers.wo"])
        x = _norm(h, get(lw, "norm2.g"), get(lw, "norm2.b"), z)
        u = es("btd,df->btf", x, lw["layers.wi"])
        if z["gated"]:
            u = _act(z["act"], es("btd,df->btf", x, lw["layers.wg"])) * u
        else:
            u = _act(z["act"], u)
        return h + es("btf,fd->btd", u, lw["layers.wo_mlp"]), None

    h, _ = jax.lax.scan(jax.checkpoint(body), h,
                        {k: w[k] for k in layer_keys})
    return _norm(h, w["final_norm.g"], w.get("final_norm.b"), z)


def _head(w):
    return w["lm_head"] if "lm_head" in w else w["embed"].T


def loss_mean(w, tokens, labels, c: dict, mode: str = "fp32"):
    """Mean next-token cross-entropy over the batch, one row at a time
    through the head."""
    es = _einsum(mode)
    h = hidden(w, tokens, c, mode)

    @jax.checkpoint
    def row(hl):
        hr, lr = hl
        logits = es("td,dv->tv", hr, _head(w))
        lse = jax.nn.logsumexp(logits, -1)
        return jnp.sum(lse - jnp.take_along_axis(logits, lr[:, None],
                                                 -1)[:, 0])
    return jnp.sum(jax.lax.map(row, (h, labels))) / labels.size


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
