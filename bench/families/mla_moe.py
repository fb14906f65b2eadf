"""The DeepSeek-V2 family: multi-head latent attention (MLA) with YaRN
rotary positions in every layer; the first ``first_k_dense_replace``
layers end in a dense gated MLP, the rest in a mixture of experts (a
softmax router over ``n_router_experts``, greedy top-k, shared experts
beside the routed ones) with the sequence-wise expert-balance loss.

A configuration may hold a chip's share of the routed experts
(``n_routed_experts`` below ``n_router_experts``): the router keeps its
published width and top-k, and the layer gives only the held experts'
part of the result (experts 0 .. n_routed_experts - 1), in the program
and in this reference alike. The balance loss is over every routed
expert, as the router sees them.

The plain reference follows DeepSeek-V2 (arXiv:2405.04434) and the
published ``modeling_deepseek.py`` equations, not the program:

* MLA without a q latent: q = x W_q, split into a 128-wide "nope" and a
  64-wide rotary part per head; [c_kv, k_rope] = x W_kv_a, c_kv
  RMS-normed; k_nope = c_kv W_k_b and v = c_kv W_v_b per head; k_rope
  shared by the heads;
* YaRN rotary on the rotary parts (arXiv:2309.00071): frequencies
  theta^(-2i/d) kept below the correction range and divided by the
  factor above it, blended linearly inside; the pairs rotated are
  (2i, 2i + 1), which is the published layout's permute-then-rotate-
  halves; softmax scale mscale(all_dim)^2 / sqrt(d_q);
* the MoE as a dense sum over the held experts, each weighted by its
  gate (zero where the token did not choose it), plus the shared
  experts; gates are the router's softmax probabilities of the chosen
  experts, renormalised only with ``norm_topk_prob``, times
  ``routed_scaling_factor``;
* the balance loss per sequence: alpha * sum_e (count_e / (T k / E)) *
  mean_t p_{t,e}, averaged over the sequences, added to the mean
  cross-entropy.

Memory: each stack of layers under a scan that keeps only the layers'
inputs for the backward; in a layer one
sequence at a time (recomputed in the backward), the held experts one at
a time, attention blocked over queries (each block takes its exact
softmax over every key, the later ones masked), and the final norm and
head one sequence at a time, so the reference fits beside its weights,
AdamW's moments and its gradient on one chip at 8 x 8192.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from bench.reference import einsum

#: Query rows per block of the reference's attention.
ATTN_BLOCK = 128
#: The program's fixed RMSNorm epsilon.
_EPS = 1e-6


def dims(c: dict) -> dict:
    """The sizes of a configuration file, under short names."""
    L, k_dense = c["num_hidden_layers"], c["first_k_dense_replace"]
    y = c["rope_scaling"]
    return dict(
        L=L, Ld=k_dense, Lm=L - k_dense, d=c["hidden_size"],
        H=c["num_attention_heads"], F=c["intermediate_size"],
        V=c["vocab_size"], R=c["kv_lora_rank"],
        dn=c["qk_nope_head_dim"], dr=c["qk_rope_head_dim"],
        dv=c["v_head_dim"], f=c["moe_intermediate_size"],
        E=c["n_router_experts"], Eh=c["n_routed_experts"],
        S=c["n_shared_experts"], k=c["num_experts_per_tok"],
        alpha=c["aux_loss_alpha"], norm_topk=c["norm_topk_prob"],
        scale=c["routed_scaling_factor"], theta=c["rope_theta"],
        yarn=y)


# ---------------------------------------------------------------------- #
# the program
# ---------------------------------------------------------------------- #

#: The only values of these keys that the family (program and reference)
#: runs.
_FIXED = {"hidden_act": "silu", "scoring_func": "softmax",
          "topk_method": "greedy", "n_group": 1, "topk_group": 1,
          "moe_layer_freq": 1, "attention_bias": False,
          "tie_word_embeddings": False, "seq_aux": True,
          "q_lora_rank": None, "rms_norm_eps": _EPS}


def arch(c: dict):
    """The program's ``ArchConfig``: the registered config with every
    size the file states, the router over ``n_router_experts`` and the
    chip's share of ``n_routed_experts`` held. A configuration the
    program cannot run as stated is refused, and so is a program whose
    MoE layer cannot hold a share of the experts."""
    from repro.configs import get_config
    from repro.models import base
    for key, want in _FIXED.items():
        if c.get(key) != want:
            raise ValueError(f"{key} {c.get(key)!r}: the family runs "
                             f"{want!r}")
    need = {"n_held", "norm_topk", "aux_form"}
    have = {f.name for f in dataclasses.fields(base.MoECfg)}
    if not need <= have or not hasattr(base, "YarnCfg"):
        raise ValueError("the program's MoE layer cannot hold a share of "
                         "the experts (MoECfg lacks "
                         f"{sorted(need - have)}) or has no YaRN rotary")
    z = dims(c)
    y = z["yarn"]
    if y.get("type") != "yarn":
        raise ValueError(f"rope_scaling {y!r} is not YaRN")
    reg = get_config(c["program_config"])
    return dataclasses.replace(
        reg, n_layers=z["L"], d_model=z["d"], n_heads=z["H"],
        n_kv_heads=z["H"], d_ff=z["F"], vocab_size=z["V"], norm="rmsnorm",
        act="silu", gated_mlp=True, rope_theta=z["theta"],
        rope_scaling=base.YarnCfg(
            factor=float(y["factor"]),
            original_max_pos=y["original_max_position_embeddings"],
            beta_fast=float(y["beta_fast"]), beta_slow=float(y["beta_slow"]),
            mscale=float(y["mscale"]),
            mscale_all_dim=float(y["mscale_all_dim"])),
        tie_embeddings=False, attn_bias=False, qk_norm=False,
        sliding_window=0, arch_type="moe", mixer_pattern=(),
        ffn_pattern=(), mtp_depth=0,
        mla=base.MLACfg(kv_lora_rank=z["R"], q_lora_rank=0,
                        qk_nope_dim=z["dn"], qk_rope_dim=z["dr"],
                        v_dim=z["dv"]),
        moe=base.MoECfg(
            n_experts=z["E"], top_k=z["k"], d_expert=z["f"],
            n_shared=z["S"], score_fn="softmax", norm_topk=z["norm_topk"],
            routed_scale=float(z["scale"]), aux_form="seq",
            aux_loss_coef=z["alpha"], z_loss_coef=0.0,
            n_held=0 if z["Eh"] == z["E"] else z["Eh"],
            first_dense=z["Ld"], period=1))


def _mla_block(w, pre):
    return {"w_q": w[pre + "wq"], "w_dkv": w[pre + "wkv_a"],
            "kv_norm": w[pre + "kv_norm.g"], "w_uk": w[pre + "wk_b"],
            "w_uv": w[pre + "wv_b"], "wo": w[pre + "wo"]}


def _mlp(w, pre, wo="wo"):
    return {"wi": w[pre + "wi"], "wg": w[pre + "wg"], "wo": w[pre + wo]}


def to_program(w: Dict[str, jax.Array], c: dict) -> dict:
    """Canonical weights -> the program's decoder tree: segment 0 the
    dense layers, segment 1 the MoE layers, each stacked on axis 0; the
    router in float32."""
    def block(kind):
        pre = f"layers.{kind}."
        b = {"norm1": {"g": w[pre + "norm1.g"]},
             "mixer": _mla_block(w, pre),
             "norm2": {"g": w[pre + "norm2.g"]}}
        if kind == "dense":
            b["ffn"] = _mlp(w, pre, "wo_mlp")
        else:
            b["ffn"] = {"w_router": _f32(w[pre + "router"]),
                        "w_up": w[pre + "experts.w_up"],
                        "w_down": w[pre + "experts.wo"],
                        "shared": _mlp(w, pre + "shared.")}
        return {"pos0": b}
    return {"embed": w["embed"], "final_norm": {"g": w["final_norm.g"]},
            "lm_head": w["lm_head"],
            "segments": {"seg0": block("dense"), "seg1": block("moe")}}


def _f32(x):
    """The router's weights in float32 (a leaf name, from
    ``weights.program_names``, passes through)."""
    return x if isinstance(x, str) else x.astype(jnp.float32)


# ---------------------------------------------------------------------- #
# the seed's weights
# ---------------------------------------------------------------------- #

def canonical_shapes(c: dict) -> Dict[str, Tuple[int, ...]]:
    z = dims(c)
    d, H = z["d"], z["H"]
    s = {"embed": (z["V"], d), "lm_head": (d, z["V"]),
         "final_norm.g": (d,)}
    for kind, n in (("dense", z["Ld"]), ("moe", z["Lm"])):
        pre = f"layers.{kind}."
        s.update({pre + "norm1.g": (n, d), pre + "norm2.g": (n, d),
                  pre + "wq": (n, d, H * (z["dn"] + z["dr"])),
                  pre + "wkv_a": (n, d, z["R"] + z["dr"]),
                  pre + "kv_norm.g": (n, z["R"]),
                  pre + "wk_b": (n, z["R"], H * z["dn"]),
                  pre + "wv_b": (n, z["R"], H * z["dv"]),
                  pre + "wo": (n, H * z["dv"], d)})
    pre = "layers.dense."
    s.update({pre + "wg": (z["Ld"], d, z["F"]),
              pre + "wi": (z["Ld"], d, z["F"]),
              pre + "wo_mlp": (z["Ld"], z["F"], d)})
    pre, n, fs = "layers.moe.", z["Lm"], z["S"] * z["f"]
    s.update({pre + "router": (n, d, z["E"]),
              # each expert's gate and up matrices side by side, gate first
              pre + "experts.w_up": (n, z["Eh"], d, 2 * z["f"]),
              pre + "experts.wo": (n, z["Eh"], z["f"], d),
              pre + "shared.wg": (n, d, fs), pre + "shared.wi": (n, d, fs),
              pre + "shared.wo": (n, fs, d)})
    return s


def init(name: str, key, shape):
    """One leaf in fp32: norm gains near 1, matrices normal with std
    1/sqrt(fan-in) (0.02 for the embedding and head)."""
    x = jax.random.normal(key, shape, jnp.float32)
    if name.endswith(".g"):
        return 1.0 + 0.05 * x
    if name in ("embed", "lm_head"):
        return 0.02 * x
    return x * (shape[-2] ** -0.5)


# ---------------------------------------------------------------------- #
# the plain reference
# ---------------------------------------------------------------------- #

def _rms(x, g):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + _EPS) * g


def _mscale(factor, m):
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(z) -> jax.Array:
    """YaRN's inverse frequencies of the ``dr``-wide rotary part."""
    y, dr, theta = z["yarn"], z["dr"], z["theta"]
    base = theta ** (jnp.arange(0, dr, 2, dtype=jnp.float32) / dr)

    def pair(rot):       # the pair index that turns ``rot`` times
        return (dr * math.log(y["original_max_position_embeddings"]
                              / (rot * 2 * math.pi))
                / (2 * math.log(theta)))
    low = max(math.floor(pair(y["beta_fast"])), 0)
    high = min(math.ceil(pair(y["beta_slow"])), dr - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dr // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    extra, inter = 1.0 / base, 1.0 / (y["factor"] * base)
    return inter * ramp + extra * (1.0 - ramp)


def softmax_scale(z) -> float:
    y = z["yarn"]
    m = _mscale(y["factor"], y["mscale_all_dim"])
    return m * m / math.sqrt(z["dn"] + z["dr"])


def _rope(x, z):
    """x (T, heads, dr): pairs (2i, 2i+1) turned by position * inv[i],
    cos and sin times mscale / mscale_all_dim."""
    y = z["yarn"]
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] \
        * yarn_inv_freq(z)
    m = (_mscale(y["factor"], y["mscale"])
         / _mscale(y["factor"], y["mscale_all_dim"]))
    cos = (jnp.cos(ang) * m)[:, None, :]
    sin = (jnp.sin(ang) * m)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     -1).reshape(x.shape)


def _attention(q, k, v, scale, es):
    """One sequence, causal: q/k (T, H, dq), v (T, H, dv), blocked over
    queries; each block takes its exact softmax over every key, the
    later ones masked."""
    T = q.shape[0]
    b = min(ATTN_BLOCK, T)

    def block(a):
        qb, i0 = a
        s = es("qhd,khd->hqk", qb, k) * scale
        rows = i0 + jnp.arange(b)[:, None]
        s = jnp.where(rows >= jnp.arange(T)[None, :], s, -jnp.inf)
        return es("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
    out = jax.lax.map(jax.checkpoint(block),
                      (q.reshape(T // b, b, *q.shape[1:]),
                       jnp.arange(0, T, b)))
    return out.reshape(T, *out.shape[2:])


def _mla(x, lw, pre, z, es):
    T, H = x.shape[0], z["H"]
    q = es("td,dn->tn", x, lw[pre + "wq"]).reshape(T, H, z["dn"] + z["dr"])
    kv = es("td,dn->tn", x, lw[pre + "wkv_a"])
    c_kv = _rms(kv[:, :z["R"]], lw[pre + "kv_norm.g"])
    k_pe = _rope(kv[:, None, z["R"]:], z)
    k_nope = es("tr,rn->tn", c_kv, lw[pre + "wk_b"]).reshape(T, H, z["dn"])
    v = es("tr,rn->tn", c_kv, lw[pre + "wv_b"]).reshape(T, H, z["dv"])
    q = jnp.concatenate([q[..., :z["dn"]], _rope(q[..., z["dn"]:], z)], -1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe, (T, H, z["dr"]))], -1)
    o = _attention(q, k, v, softmax_scale(z), es)
    return es("tn,nd->td", o.reshape(T, H * z["dv"]), lw[pre + "wo"])


def _gated(x, wg, wi, wo, es):
    return es("tf,fd->td", jax.nn.silu(es("td,df->tf", x, wg))
              * es("td,df->tf", x, wi), wo)


def _moe(x, lw, z, es):
    """(held experts' part + shared experts, balance loss) of one
    sequence's MoE layer; the held experts one at a time."""
    pre = "layers.moe."
    T, E, f = x.shape[0], z["E"], z["f"]
    probs = jax.nn.softmax(es("td,de->te", x, lw[pre + "router"]), -1)
    top, idx = jax.lax.top_k(probs, z["k"])
    chosen = jnp.sum(jax.nn.one_hot(idx, E, dtype=jnp.float32), 1)
    gates = probs * chosen
    if z["norm_topk"]:
        gates = gates / jnp.sum(top, -1, keepdims=True)
    gates = gates[:, :z["Eh"]] * z["scale"]

    @jax.checkpoint
    def expert(acc, e):
        w_up, wo, g = e
        return acc + _gated(x, w_up[:, :f], w_up[:, f:], wo, es) \
            * g[:, None], None
    out, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                          (lw[pre + "experts.w_up"],
                           lw[pre + "experts.wo"], gates.T))
    out = out + _gated(x, lw[pre + "shared.wg"], lw[pre + "shared.wi"],
                       lw[pre + "shared.wo"], es)
    ce = jnp.sum(chosen, 0) / (T * z["k"] / E)
    aux = z["alpha"] * jnp.sum(ce * jnp.mean(probs, 0))
    return out, aux


def _rows(f, *xs):
    """``f`` on one sequence at a time, each recomputed in the backward."""
    return jax.lax.map(jax.checkpoint(lambda a: f(*a)), xs)


def _layer(hr, lw, kind, z, es):
    """One sequence (T, d) through one layer -> (its output, its balance
    loss)."""
    pre = f"layers.{kind}."
    h = hr + _mla(_rms(hr, lw[pre + "norm1.g"]), lw, pre, z, es)
    x = _rms(h, lw[pre + "norm2.g"])
    if kind == "dense":
        return h + _gated(x, lw[pre + "wg"], lw[pre + "wi"],
                          lw[pre + "wo_mlp"], es), jnp.zeros((), jnp.float32)
    o, aux = _moe(x, lw, z, es)
    return h + o, aux


def _stack(h, w, kind, z, es):
    """The ``layers.<kind>.*`` stack over (B, T, d) under a scan, a
    sequence at a time in each layer -> (output, each sequence's balance
    loss summed over the stack (B,)). The backward keeps each layer's
    input: the sequences are recomputed from it."""
    pre = f"layers.{kind}."

    def body(h, lw):
        return _rows(lambda hr: _layer(hr, lw, kind, z, es), h)
    h, aux = jax.lax.scan(body, h, {k: v for k, v in w.items()
                                    if k.startswith(pre)})
    return h, jnp.sum(aux, 0)


def hidden(w, tokens, z, es):
    """tokens (B, T) -> (hidden states before the final norm (B, T, d),
    each sequence's balance loss summed over the MoE layers (B,)). Only
    the layers' inputs are kept for the backward: the embedding and the
    dense layers are recomputed from the tokens."""
    h = jax.checkpoint(lambda w, t: _stack(w["embed"][t], w, "dense", z,
                                           es)[0])(w, tokens)
    return _stack(h, w, "moe", z, es)


def loss_mean(w, tokens, labels, c: dict, mode: str = "fp32"):
    """Mean next-token cross-entropy over the batch plus the mean over
    its sequences of the balance loss; the final norm and the head one
    sequence at a time."""
    z = dims(c)
    es = einsum(mode)
    h, aux = hidden(w, tokens, z, es)

    def row(hr, lr):
        logits = es("td,dv->tv", _rms(hr, w["final_norm.g"]), w["lm_head"])
        lse = jax.nn.logsumexp(logits, -1)
        return jnp.sum(lse - jnp.take_along_axis(logits, lr[:, None],
                                                 -1)[:, 0])
    return jnp.sum(_rows(row, h, labels)) / labels.size + jnp.mean(aux)


def reference_spec(name: str, shape) -> P:
    """The embedding and head (on their last dim) and the held experts
    split over the chips; the rest replicated."""
    if name in ("embed", "lm_head"):
        return P(None, "tp")
    if ".experts." in name:
        return P(None, "tp", None, None)
    return P()


# ---------------------------------------------------------------------- #
# operations per token
# ---------------------------------------------------------------------- #

def mla_weights(z) -> int:
    H = z["H"]
    return z["d"] * (H * (z["dn"] + z["dr"]) + z["R"] + z["dr"]) \
        + z["R"] * H * (z["dn"] + z["dv"]) + H * z["dv"] * z["d"]


def routed_rows_per_token(z) -> float:
    """Expected held-expert evaluations a token makes: top_k times the
    held share of the router's experts."""
    return z["k"] * z["Eh"] / z["E"]


def matmul_weights(c: dict) -> float:
    """Weights that multiply every token, the routed experts at their
    expected share."""
    z = dims(c)
    d, f = z["d"], z["f"]
    moe = d * z["E"] + 3 * d * f * (z["S"] + routed_rows_per_token(z))
    return (z["L"] * mla_weights(z) + z["Ld"] * 3 * d * z["F"]
            + z["Lm"] * moe + d * z["V"])


def attention_fwd_flops(c: dict, seq: int) -> float:
    """QK^T at d_q (nope + rope) and PV at d_v, causal."""
    z = dims(c)
    return z["L"] * z["H"] * (z["dn"] + z["dr"] + z["dv"]) * (seq + 1)


def train_flops_per_token(c: dict, seq: int) -> float:
    return 3.0 * (2.0 * matmul_weights(c) + attention_fwd_flops(c, seq))


def experts_kernel_work(c: dict, tokens: float) -> Tuple[float, float]:
    """(FLOPs, bytes) of the routed experts' grouped products over
    ``tokens`` tokens at the expected held load: per MoE layer the up
    (d -> 2f) and down (f -> d) products, each run forward, recomputed
    (full rematerialization) and twice in the backward (dX, and dW by
    ``tgmm``); each call reads its rows and the held experts' matrices
    once and writes its result once, in bf16."""
    z = dims(c)
    rows = tokens * routed_rows_per_token(z)
    flops = bytes_ = 0.0
    for k, n in ((z["d"], 2 * z["f"]), (z["f"], z["d"])):
        flops += 4 * 2.0 * rows * k * n
        bytes_ += 4 * 2.0 * (rows * k + rows * n + z["Eh"] * k * n)
    return z["Lm"] * flops, z["Lm"] * bytes_


# ---------------------------------------------------------------------- #
# sizes for the CPU tests
# ---------------------------------------------------------------------- #

def _tiny(held: int, alpha: float, norm_topk: bool = False):
    return {"program_config": "deepseek-v2-lite-16b", "family": "mla_moe",
            "hidden_size": 64, "intermediate_size": 128,
            "num_attention_heads": 4, "num_key_value_heads": 4,
            "num_hidden_layers": 3, "first_k_dense_replace": 1,
            "moe_layer_freq": 1, "vocab_size": 512, "kv_lora_rank": 32,
            "q_lora_rank": None, "qk_nope_head_dim": 16,
            "qk_rope_head_dim": 8, "v_head_dim": 8,
            "moe_intermediate_size": 32, "n_router_experts": 16,
            "n_routed_experts": held, "n_shared_experts": 2,
            "num_experts_per_tok": 6, "norm_topk_prob": norm_topk,
            "routed_scaling_factor": 1, "scoring_func": "softmax",
            "topk_method": "greedy", "n_group": 1, "topk_group": 1,
            "seq_aux": True, "aux_loss_alpha": alpha, "hidden_act": "silu",
            "attention_bias": False, "rms_norm_eps": _EPS,
            "rope_theta": 10000, "tie_word_embeddings": False,
            "rope_scaling": {"type": "yarn", "factor": 40,
                             "original_max_position_embeddings": 16,
                             "beta_fast": 32, "beta_slow": 1,
                             "mscale": 0.707, "mscale_all_dim": 0.707},
            "torch_dtype": "bfloat16"}


def tiny_configs() -> Dict[str, dict]:
    """MLA with YaRN and the MoE, at widths a test can hold: a chip's
    share of the experts (8 of 16, the cell's kind, with a balance loss
    large enough to show in the gradients), all experts held, and the
    renormalised top-k gates."""
    return {"mla-yarn-moe-held-8-of-16": _tiny(8, 0.1),
            "mla-yarn-moe-all-16-held": _tiny(16, 0.001),
            "mla-yarn-moe-norm-topk": _tiny(16, 0.01, norm_topk=True)}
