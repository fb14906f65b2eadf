"""The dense decoder family: every layer an (attention, MLP) block in one
scanned segment. LayerNorm or RMSNorm, partial rotary embeddings, causal
multi-head or grouped-query attention, gated or plain MLP, tied or
untied head. The family of a configuration file without ``family``.

The plain reference follows the published descriptions with one
departure that the program shares: the rotary pairs are interleaved
(dimensions 2i and 2i+1) where the Hugging Face models rotate halves.
The two are the same model up to a fixed permutation of the query and
key columns. Memory: the layers run under a scan with rematerialization,
attention one row of the batch at a time, and the head and loss one row
at a time, so the reference fits beside nothing else on the program's
chips.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from bench.reference import einsum

#: The program's activations, by the configuration file's name.
_ACT = {"silu": "silu", "gelu_new": "gelu", "gelu_pytorch_tanh": "gelu"}
#: The program's fixed norm epsilons.
_EPS = {"layernorm": 1e-5, "rmsnorm": 1e-6}
#: Mechanisms of a registered config that a dense model does not have.
_NOT_DENSE = ("moe", "mla", "mamba", "xlstm", "encoder", "mixer_pattern",
              "ffn_pattern", "mtp_depth")


def dims(c: dict) -> dict:
    """The sizes of a configuration file, under short names."""
    d = c["hidden_size"]
    H = c["num_attention_heads"]
    return dict(L=c["num_hidden_layers"], d=d, H=H,
                KV=c.get("num_key_value_heads", H),
                hd=c.get("head_dim", d // H), F=c["intermediate_size"],
                V=c["vocab_size"], gated=c["mlp_gated"],
                norm=c["norm_type"], eps=c["layer_norm_eps"],
                act=c["hidden_act"], rot=c.get("partial_rotary_factor", 1.0),
                theta=c.get("rope_theta", 10000.0),
                tied=c.get("tie_word_embeddings", False))


# ---------------------------------------------------------------------- #
# the program
# ---------------------------------------------------------------------- #

def arch(c: dict):
    """The program's ``ArchConfig`` for a configuration file: its
    registered config with every size the file states put in. A
    registered config with a mechanism of another family is refused,
    not run as a dense model."""
    from repro.configs import get_config
    z = dims(c)
    reg = get_config(c["program_config"])
    present = [k for k in _NOT_DENSE if getattr(reg, k)]
    if present:
        raise ValueError(f"{c['program_config']!r} has {', '.join(present)}:"
                         f" not a dense model; its configuration file "
                         f"names its family")
    if c.get("use_qkv_bias"):
        raise ValueError("the program has no attention with q/k/v biases "
                         "only")
    if z["act"] not in _ACT:
        raise ValueError(f"activation {z['act']!r} is not in the program")
    if _EPS[z["norm"]] != z["eps"]:
        raise ValueError(f"the program's {z['norm']} uses eps "
                         f"{_EPS[z['norm']]}, the configuration {z['eps']}")
    return dataclasses.replace(
        reg, n_layers=z["L"], d_model=z["d"], n_heads=z["H"],
        n_kv_heads=z["KV"], head_dim=z["hd"], d_ff=z["F"], vocab_size=z["V"],
        norm=z["norm"], act=_ACT[z["act"]], gated_mlp=z["gated"],
        rotary_pct=z["rot"], rope_theta=z["theta"], tie_embeddings=z["tied"],
        attn_bias=False, qk_norm=False, sliding_window=0, arch_type="dense")


def to_program(w: Dict[str, jax.Array], c: dict) -> dict:
    """Canonical weights -> the program's decoder parameter tree
    (``repro.models.decoder.decoder_init``: one scanned segment of
    (attention, MLP) blocks, layers stacked on axis 0)."""
    z = dims(c)

    def norm(prefix):
        out = {"g": w[prefix + ".g"]}
        if z["norm"] == "layernorm":
            out["b"] = w[prefix + ".b"]
        return out

    ffn = {"wi": w["layers.wi"], "wo": w["layers.wo_mlp"]}
    if z["gated"]:
        ffn["wg"] = w["layers.wg"]
    block = {"norm1": norm("layers.norm1"),
             "mixer": {k: w["layers." + k] for k in ("wq", "wk", "wv", "wo")},
             "norm2": norm("layers.norm2"), "ffn": ffn}
    tree = {"embed": w["embed"], "segments": {"seg0": {"pos0": block}},
            "final_norm": norm("final_norm")}
    if not z["tied"]:
        tree["lm_head"] = w["lm_head"]
    return tree


# ---------------------------------------------------------------------- #
# the seed's weights
# ---------------------------------------------------------------------- #

def canonical_shapes(c: dict) -> Dict[str, Tuple[int, ...]]:
    z = dims(c)
    L, d, F = z["L"], z["d"], z["F"]
    s = {"embed": (z["V"], d),
         "layers.wq": (L, d, z["H"] * z["hd"]),
         "layers.wk": (L, d, z["KV"] * z["hd"]),
         "layers.wv": (L, d, z["KV"] * z["hd"]),
         "layers.wo": (L, z["H"] * z["hd"], d),
         "layers.wi": (L, d, F),
         "layers.wo_mlp": (L, F, d)}
    if z["gated"]:
        s["layers.wg"] = (L, d, F)
    norms = ["layers.norm1", "layers.norm2", "final_norm"]
    for n in norms:
        shape = (d,) if n == "final_norm" else (L, d)
        s[n + ".g"] = shape
        if z["norm"] == "layernorm":
            s[n + ".b"] = shape
    if not z["tied"]:
        s["lm_head"] = (d, z["V"])
    return s


def init(name: str, key, shape):
    """One leaf in fp32: norm gains near 1, norm biases near 0, matrices
    normal with std 1/sqrt(fan-in) (0.02 for the embedding and head)."""
    x = jax.random.normal(key, shape, jnp.float32)
    if name.endswith(".g"):
        return 1.0 + 0.05 * x
    if name.endswith(".b"):
        return 0.05 * x
    if name in ("embed", "lm_head"):
        return 0.02 * x
    return x * (shape[-2] ** -0.5)


# ---------------------------------------------------------------------- #
# the plain reference
# ---------------------------------------------------------------------- #

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
    z = dims(c)
    es = einsum(mode)
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
    es = einsum(mode)
    h = hidden(w, tokens, c, mode)

    @jax.checkpoint
    def row(hl):
        hr, lr = hl
        logits = es("td,dv->tv", hr, _head(w))
        lse = jax.nn.logsumexp(logits, -1)
        return jnp.sum(lse - jnp.take_along_axis(logits, lr[:, None],
                                                 -1)[:, 0])
    return jnp.sum(jax.lax.map(row, (h, labels))) / labels.size


#: Matrices split over the chips on their output width, and on their
#: input width; every other leaf is replicated.
_SPLIT_OUT = ("layers.wq", "layers.wk", "layers.wv", "layers.wi",
              "layers.wg", "lm_head", "embed")
_SPLIT_IN = ("layers.wo", "layers.wo_mlp")


def reference_spec(name: str, shape) -> P:
    if name in _SPLIT_OUT:
        return P(*([None] * (len(shape) - 1)), "tp")
    if name in _SPLIT_IN:
        return P(None, "tp", None)
    return P()


# ---------------------------------------------------------------------- #
# operations per token
# ---------------------------------------------------------------------- #

def matmul_weights(c: dict) -> int:
    """Weights that multiply every token (layers and head)."""
    z = dims(c)
    attn = z["d"] * z["hd"] * (2 * z["H"] + 2 * z["KV"])
    mlp = z["d"] * z["F"] * (3 if z["gated"] else 2)
    return z["L"] * (attn + mlp) + z["d"] * z["V"]


def attention_fwd_flops(c: dict, seq: int) -> float:
    z = dims(c)
    return z["L"] * 2.0 * z["H"] * z["hd"] * (seq + 1)


def train_flops_per_token(c: dict, seq: int) -> float:
    return 3.0 * (2.0 * matmul_weights(c) + attention_fwd_flops(c, seq))


# ---------------------------------------------------------------------- #
# sizes for the CPU tests
# ---------------------------------------------------------------------- #

def _tiny(norm="layernorm", gated=True, act="silu", kv=4, rot=0.25):
    return {"program_config": "stablelm-1.6b", "hidden_size": 64,
            "intermediate_size": 128, "num_attention_heads": 4,
            "num_key_value_heads": kv, "head_dim": 16,
            "num_hidden_layers": 2, "vocab_size": 512,
            "hidden_act": act, "mlp_gated": gated, "norm_type": norm,
            "layer_norm_eps": 1e-5 if norm == "layernorm" else 1e-6,
            "partial_rotary_factor": rot, "rope_theta": 10000,
            "tie_word_embeddings": False, "torch_dtype": "bfloat16"}


def tiny_configs() -> Dict[str, dict]:
    """Each kind of layer the family covers, at widths a test can hold:
    the one-chip cell's kind, the four-chip cell's, and the other norm,
    activation, grouped-query and full-rotary paths."""
    return {
        "layernorm-gated-mha-partial-rotary": _tiny(),
        "layernorm-plain-gelu-mha-partial-rotary": _tiny(
            gated=False, act="gelu_new"),
        "rmsnorm-plain-gelu-gqa-full-rotary": _tiny(
            norm="rmsnorm", gated=False, act="gelu_new", kv=2, rot=1.0),
    }
