"""The dense weights generator as it stood before model families, kept
verbatim: the seed's weights of a dense configuration have to stay bit
for bit what they were (``test_bench_families``)."""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp


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


def _leaf(key, name: str, shape):
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


DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def dtype(c: dict):
    """The type the configuration runs in (``torch_dtype``)."""
    return DTYPES[c["torch_dtype"]]


def canonical(c: dict, key) -> Dict[str, jax.Array]:
    """The canonical weights of ``key`` = ``common.base_key(seed)`` in the
    configuration's type (traceable; call under jit with the key as an
    argument, so that one compiled program serves every seed)."""
    dt = dtype(c)
    out = {}
    for i, (name, shape) in enumerate(sorted(canonical_shapes(c).items())):
        out[name] = _leaf(jax.random.fold_in(key, i), name,
                          shape).astype(dt)
    return out
