"""Mixture-of-Experts under the 4D layout.

Expert placement exploits the paper's activation layout: the residual
stream is *replicated over y*, so sharding the expert bank over ``y`` makes
dispatch communication-free within the tensor group — every y-rank already
holds every token and simply computes its own E/G_y experts. The only
collective the MoE layer adds over a dense MLP is the final combine
all-reduce over ``y``, which *replaces* (at identical volume) the down
projection's all-reduce — plus the tiny router all-reduce over ``x``.
This is recorded in DESIGN.md as a consequence of the paper's layout, not
an extra trick: under Megatron-style 1D TP the same MoE needs either
expert all-to-alls or full activation gathers.

A chip's share of the experts (``MoECfg.n_held``): the router keeps
its published width and top-k over all ``n_experts``, while the bank
holds only experts ``0 .. n_held - 1`` (over ``y`` and ``expert`` as
below); the layer computes its own experts' part of the result for the
tokens routed to them, as one chip of an expert-parallel deployment
does. ``n_held`` 0 holds them all.

Without an expert axis (g_expert = 1) dispatch is dropless: the
(token, slot) pairs are sorted by held expert and the experts run as
grouped matrix products (``kernels.grouped_matmul``) over a static
buffer of tokens x top_k rows, the worst case, of which only the routed
rows are computed. With an expert axis the all-to-all needs static
per-destination shapes, so dispatch is capacity-based with
gather/scatter indexing (O(T*E_local) bookkeeping memory, no (T, E, C)
one-hot tensor): tokens past an expert's capacity are dropped.

The routed experts' products run in the ``experts`` layer scope inside
``moe`` (router, sort, dispatch, combine and shared experts).

Expert axis (g_expert > 1): the ``expert`` mesh axis shards the batch for
every dense layer (a second data axis) and subdivides each y-rank's
expert block — global expert ``e`` lives at y-rank ``e // (E/G_y)``,
expert-rank ``(e % (E/G_y)) // e_local`` with ``e_local =
E/(G_y*G_expert)`` (y-major, expert-inner, so the placement reduces to
today's y-only layout at g_expert = 1). Tokens reach off-rank experts in
their y block via a capacity-based dispatch buffer (g_expert, e_local,
capacity, d) exchanged with ``jax.lax.all_to_all`` over the expert axis
(combine is the reverse exchange); with ``OverlapConfig.expert_a2a`` the
round trip runs as ``collective_matmul.ring_a2a_expert`` — pairwise
ppermute exchanges interleaved with the per-source expert GEMMs, bitwise
the blocking layout with zero all-to-all HLO ops.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import collective_matmul as CMM
from repro.core import mesh as M
from repro.core import parallel as PP
from repro.core import trace
from repro.layers.mlp import _act, mlp_apply, mlp_init


def moe_init(key, cfg, axes: M.MeshAxes, *, dtype=jnp.bfloat16, stack=(),
             abstract=False):
    mc = cfg.moe
    d, f = cfg.d_model, mc.d_expert
    if mc.n_bank % (axes.gy * axes.gexpert):
        raise ValueError(f"{mc.n_bank} experts not divisible by "
                         f"G_y*G_expert={axes.gy * axes.gexpert}")
    ks = jax.random.split(key, 4)
    gated = cfg.act != "squared_relu"
    up_n = 2 * f if gated else f
    p = {
        # router: contract x, replicated logits over all routed experts
        "w_router": PP.tp_linear_init(ks[0], d, mc.n_experts, axes,
                                      in_shard="x", out_shard=None,
                                      dtype=jnp.float32, stack=stack,
                                      abstract=abstract),
        "w_up": PP.tp_expert_init(ks[1], mc.n_bank, d, up_n, axes,
                                  in_shard="x", out_shard=None, dtype=dtype,
                                  stack=stack, abstract=abstract),
        "w_down": PP.tp_expert_init(ks[2], mc.n_bank, f, d, axes,
                                    in_shard=None, out_shard="x",
                                    dtype=dtype, stack=stack,
                                    abstract=abstract),
    }
    if mc.n_shared:
        p["shared"] = mlp_init(ks[3], d, mc.n_shared * f, cfg.act, axes,
                               gated=gated, dtype=dtype, stack=stack,
                               abstract=abstract)
    return p


def _topk_gates(logits, mc):
    """Router logits (T, E) fp32, replicated -> (gates, indices), (T, k).

    * ``softmax`` with ``norm_topk`` (Mixtral, Jamba): the softmax of the
      top-k logits, so the gates sum to 1;
    * ``softmax`` without ``norm_topk`` (DeepSeek-V2): a softmax over all
      E experts and its greedy top-k values, times ``routed_scale``; they
      sum to less than 1;
    * ``sigmoid`` (DeepSeek-V3): sigmoid scores, top-k, renormalised,
      times ``routed_scale``."""
    if mc.score_fn == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        vals, idx = jax.lax.top_k(scores, mc.top_k)
        gates = vals / (jnp.sum(vals, -1, keepdims=True) + 1e-20)
        gates = gates * mc.routed_scale
    elif mc.norm_topk:
        vals, idx = jax.lax.top_k(logits, mc.top_k)
        gates = jax.nn.softmax(vals, axis=-1)
    else:
        gates, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1),
                                   mc.top_k)
        gates = gates * mc.routed_scale
    return gates, idx


def _aux_losses(logits, idx, mc, n_seq: int = 1):
    """The load-balance loss of the router (replicated values), plus the
    router z-loss where ``z_loss_coef`` is set. logits (n_seq * T, E),
    sequence-major.

    * ``switch``: E * sum_e (mean router prob) * (load fraction) over
      every token of the batch;
    * ``seq`` (DeepSeek-V2's sequence-wise loss): per sequence
      ce_e = count_e / (T * k / E), then the mean over sequences of
      sum_e ce_e * (mean over the sequence's tokens of p_e)."""
    T, E = logits.shape
    probs = jax.nn.softmax(logits, axis=-1)
    counts = jnp.sum(jax.nn.one_hot(idx, E, dtype=jnp.float32), axis=1)
    if mc.aux_form == "seq":
        t = T // n_seq
        ce = jnp.sum(counts.reshape(n_seq, t, E), axis=1) / (
            t * mc.top_k / E)
        pm = jnp.mean(probs.reshape(n_seq, t, E), axis=1)
        lb = jnp.mean(jnp.sum(ce * pm, axis=-1))
    else:
        me = jnp.mean(probs, axis=0)                   # mean router prob
        ce = jnp.mean(counts, axis=0) / mc.top_k       # load frac
        lb = E * jnp.sum(me * ce)
    aux = mc.aux_loss_coef * lb
    if mc.z_loss_coef:
        z = jnp.mean(jax.scipy.special.logsumexp(logits, axis=-1) ** 2)
        aux = aux + mc.z_loss_coef * z
    return aux


def _counters(routed, loads, dropped, n_slots):
    """(held load share, largest held load / mean held load, dropped
    slots) as float32[3]: the slots routed to this rank's experts over
    tokens x top_k, the balance of their loads, and the routed slots that
    no expert computed."""
    loads = loads.astype(jnp.float32)
    mean = jnp.mean(loads)
    return jnp.stack([routed.astype(jnp.float32) / n_slots,
                      jnp.where(mean > 0, jnp.max(loads) / mean, 0.0),
                      dropped.astype(jnp.float32)])


def _expert_ffn(p, xs, sizes, cfg, axes):
    """Held experts' gated FFN over rows sorted by expert (grouped
    products, 4D tp inside each expert)."""
    u = PP.tp_grouped_matmul(xs, p["w_up"], sizes, axes, "x", None)
    if cfg.act != "squared_relu":
        g, u2 = jnp.split(u, 2, axis=-1)
        hidden = _act(cfg.act, g) * u2
    else:
        hidden = _act(cfg.act, u)
    return PP.tp_grouped_matmul(hidden, p["w_down"], sizes, axes, None, "x")


def _dropless(p, hf, gates, idx, cfg, axes, e_local, first):
    """Every slot routed to a held expert is computed: sort the (token,
    slot) pairs by held expert (the rest last), run the grouped products
    over the sorted rows, and put each row back under its slot."""
    n_tok, k = idx.shape
    n = n_tok * k
    local = idx.reshape(-1) - first
    held = (local >= 0) & (local < e_local)
    key = jnp.where(held, local, e_local)
    order = jnp.argsort(key, stable=True)
    loads = jnp.zeros(e_local + 1, jnp.int32).at[key].add(1)[:e_local]
    routed = jnp.sum(held)
    valid = (jnp.arange(n) < routed)[:, None]
    xs = jnp.where(valid, jnp.take(hf, order // k, axis=0), 0)
    with trace.layer("experts"):
        ys = _expert_ffn(p, xs, loads, cfg, axes)
    ys = jnp.where(valid, ys, 0)
    slot_row = jnp.zeros(n, jnp.int32).at[order].set(
        jnp.arange(n, dtype=jnp.int32), unique_indices=True)
    y_slots = jnp.take(ys, slot_row, axis=0).reshape(n_tok, k, -1)
    out = jnp.sum(y_slots.astype(jnp.float32) * gates[..., None], axis=1)
    stats = _counters(routed, loads, routed - jnp.sum(loads), n)
    return out.astype(hf.dtype), stats


def _capacity(p, hf, gates, idx, cfg, axes, e_block, y_start):
    """Capacity-based dispatch over the expert axis (g_expert > 1): each
    expert takes at most ``capacity`` slots, in token order; the rest are
    dropped."""
    mc = cfg.moe
    n_tok, dx = hf.shape
    p_ex = axes.gexpert
    e_local = e_block // p_ex              # experts on this (y, ex) rank
    gated = cfg.act != "squared_relu"
    capacity = max(int(mc.capacity_factor * n_tok * mc.top_k
                       / mc.n_experts), 4)

    local = idx - y_start                              # (n_tok, k)
    ok = (local >= 0) & (local < e_block)
    eflat = jnp.where(ok, local, e_block)              # e_block = "drop"
    # position of each (token, slot) within its expert queue
    onehot = jax.nn.one_hot(eflat.reshape(-1), e_block + 1, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) - 1               # (n_tok*k, e+1)
    pos = jnp.take_along_axis(pos, eflat.reshape(-1, 1), axis=1)[:, 0]
    fits = (pos < capacity) & ok.reshape(-1)
    slot = jnp.where(fits, eflat.reshape(-1) * capacity + pos,
                     e_block * capacity)
    # token id owning each (expert, cap) slot
    tok_ids = jnp.tile(jnp.arange(n_tok)[:, None],
                       (1, mc.top_k)).reshape(-1)
    owner = jnp.zeros(e_block * capacity + 1, jnp.int32).at[slot].set(
        tok_ids, mode="drop")[:-1]
    filled = jnp.zeros(e_block * capacity + 1, jnp.bool_).at[slot].set(
        True, mode="drop")[:-1]
    gate_of_slot = jnp.zeros(e_block * capacity + 1, jnp.float32).at[
        slot].set(gates.reshape(-1), mode="drop")[:-1]

    xe = jnp.take(hf, owner, axis=0)                   # (e*cap, d/x)
    xe = jnp.where(filled[:, None], xe, 0)

    def ffn(block):
        """block (e_local, C, d/x) -> (e_local, C, d/x); gates stay at
        the source rank, applied after the combine exchange."""
        with trace.layer("experts"):
            u = PP.tp_batched_matmul(block, p["w_up"], axes, "x", None)
            if gated:
                g, u2 = jnp.split(u, 2, axis=-1)
                hidden = _act(cfg.act, g) * u2
            else:
                hidden = _act(cfg.act, u)
            return PP.tp_batched_matmul(hidden, p["w_down"], axes, None,
                                        "x")

    # dispatch buffer, dim 0 = destination expert-rank (the queue
    # index eflat = t*e_local + local_e already orders it that way)
    buf = xe.reshape(p_ex, e_local, capacity, dx)
    if axes.overlap.expert_a2a:
        out_b = CMM.ring_a2a_expert(buf, axes.expert, ffn)
    else:
        recv = M.all_to_all(buf.reshape(p_ex * e_local, capacity, dx),
                            axes.expert, dim=0)
        recv = recv.reshape(p_ex, e_local, capacity, dx).transpose(
            1, 0, 2, 3).reshape(e_local, p_ex * capacity, dx)
        y = ffn(recv)
        y = y.reshape(e_local, p_ex, capacity, dx).transpose(
            1, 0, 2, 3).reshape(p_ex * e_local, capacity, dx)
        out_b = M.all_to_all(y, axes.expert, dim=0)
    out_e = out_b.reshape(e_block * capacity, dx)
    out_e = out_e * gate_of_slot[:, None].astype(out_e.dtype)

    # combine: scatter-add back to tokens
    combined = jnp.zeros((n_tok, dx), out_e.dtype).at[owner].add(
        jnp.where(filled[:, None], out_e, 0))
    routed = jnp.sum(ok)
    loads = jnp.sum(onehot[:, :e_block], axis=0)
    stats = _counters(routed, loads, routed - jnp.sum(fits),
                      n_tok * mc.top_k)
    return combined, stats


def moe_apply(p, h, cfg, axes: M.MeshAxes):
    """h: (B, T, d/x) replicated over y, batch-sharded over (data, z,
    expert). Returns (out, aux_loss, counters): ``counters`` is
    :func:`_counters`' float32[3] of this rank's experts."""
    mc = cfg.moe
    B, T, dx = h.shape
    e_block = mc.n_bank // axes.gy         # this y-rank's expert block
    y_start = M.axis_index(axes.y) * e_block

    hf = h.reshape(B * T, dx)
    # the published gate: fp32 activations and weights, fp32 products
    with jax.default_matmul_precision("float32"):
        logits = PP.tp_matmul(hf.astype(jnp.float32), p["w_router"], axes,
                              "x", None)
    gates, idx = _topk_gates(logits, mc)               # (n_tok, k)
    aux = _aux_losses(logits, idx, mc, B)

    if axes.gexpert > 1:
        combined, stats = _capacity(p, hf, gates, idx, cfg, axes, e_block,
                                    y_start)
    else:
        combined, stats = _dropless(p, hf, gates, idx, cfg, axes, e_block,
                                    y_start)
    # each y-rank holds its own experts' part: all-reduce over y
    combined = PP.ar_bwd_identity(combined, axes.y)
    out = combined.reshape(B, T, dx)

    if "shared" in p:
        out = out + mlp_apply(p["shared"], h, cfg.act, axes,
                              gated=cfg.act != "squared_relu")
    return out, aux, stats
