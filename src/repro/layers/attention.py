"""Attention mixers under the 4D layout.

Heads are sharded over ``y`` (the output axis of the fused QKV projection,
a paper "normal" layer); the output projection is a paper "transposed"
layer (contract over ``y``, all-reduce over ``y``), returning the residual
to its x-sharded layout with zero boundary communication (§4.1).

Variants: MHA/GQA (optionally sliding-window and/or qk-norm), cross
attention (whisper), and DeepSeek MLA (low-rank latent KV, with the
absorbed-matmul decode path).

Decode supports two cache layouts:
  * batch-sharded (default): cache (B_local, S, kv_local, hd)
  * sequence-sharded over ``data`` (long-context, global_batch=1): partial
    attention per shard merged with a log-sum-exp psum — a beyond-paper
    extension recorded in DESIGN.md.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import mesh as M
from repro.core import parallel as PP
from repro.core import trace
from repro.core.partition import Boxed
from repro.kernels import tpu_attention as TA
from repro.layers.rotary import apply_rope, yarn_mscale

NEG_INF = -1e30


# ---------------------------------------------------------------------- #
# plain (replicated-param) per-head RMSNorm, used for qk-norm and MLA
# latent norms — head_dim / latent dims are never sharded.
# ---------------------------------------------------------------------- #

def _plain_rms(x, gamma, eps=1e-6):
    xf = x.astype(jnp.float32)
    ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(ms + eps) * gamma.astype(jnp.float32)
            ).astype(x.dtype)


def _softmax_fp32(scores):
    """Softmax accumulated in fp32 regardless of the activation dtype.

    Every attention path routes through this (or the fp32 (m, l, acc)
    online-softmax carries): a reduced-precision exp/sum would break the
    cross-hop rescaling parity the ring-attention schedule relies on —
    tests/test_ring_attention.py pins the bf16-vs-fp32 tolerance."""
    return jax.nn.softmax(scores.astype(jnp.float32), axis=-1)


# ---------------------------------------------------------------------- #
# attention core: jnp everywhere, the Pallas flash kernel of
# kernels/tpu_attention on the TPU where it computes the same thing
# ---------------------------------------------------------------------- #

def attn_core(q, k, v, *, causal: bool = True, window: int = 0,
              q_pos0=0, scale: Optional[float] = None,
              chunked_threshold: int = 2048):
    """q: (B, Tq, nq, d); k/v: (B, Tk, nkv, d); GQA via head grouping.

    ``q_pos0`` is the absolute position of q[:, 0] (for cached decode).
    ``window`` > 0 enables sliding-window attention (mistral-style).
    Long sequences route to the chunked online-softmax path (flash-style
    O(T*chunk) memory).

    Where ``tpu_attention.supports`` holds (bf16 self-attention from
    position 0, as many K/V heads as q heads, no window, a v head dim no
    wider than q's, a head dim and length the kernel's block rule tiles;
    any scale), a program lowered for a TPU runs the Pallas flash kernel
    instead, at any
    length; every other platform, and every other call, runs the jnp
    core, which the kernel's tests take as their oracle. The choice is made at lowering
    (``lax.platform_dependent``), so a program compiled ahead of time for
    a TPU from a CPU process gets the kernel too."""
    def jnp_core(q, k, v):
        if max(q.shape[1], k.shape[1]) > chunked_threshold:
            return attn_core_chunked(q, k, v, causal=causal, window=window,
                                     q_pos0=q_pos0, scale=scale)
        return _attn_core_dense(q, k, v, causal=causal, window=window,
                                q_pos0=q_pos0, scale=scale)

    if TA.supports(q, k, v, window=window, q_pos0=q_pos0, scale=scale):
        return jax.lax.platform_dependent(
            q, k, v, tpu=functools.partial(TA.flash_mha, causal=causal,
                                           scale=scale),
            default=jnp_core)
    return jnp_core(q, k, v)


def _attn_core_dense(q, k, v, *, causal, window, q_pos0, scale):
    """:func:`attn_core`'s jnp core at short lengths: the whole fp32
    score tensor, masked, its softmax, then PV."""
    B, Tq, nq, d = q.shape
    Tk, nkv = k.shape[1], k.shape[2]
    g = nq // nkv
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    qg = q.reshape(B, Tq, nkv, g, d)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32),
                        k.astype(jnp.float32),
                        preferred_element_type=jnp.float32) * scale
    iq = (jnp.arange(Tq) + q_pos0)[:, None]
    jk = jnp.arange(Tk)[None, :]
    mask = jnp.ones((Tq, Tk), bool)
    if causal:
        mask &= iq >= jk
    if window > 0:
        mask &= (iq - jk) < window
    scores = jnp.where(mask, scores, NEG_INF)
    probs = _softmax_fp32(scores)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    return out.reshape(B, Tq, nq, v.shape[-1]).astype(q.dtype)  # dv may != dq (MLA)


def attn_core_chunked(q, k, v, *, causal: bool = True, window: int = 0,
                      q_pos0=0, scale: Optional[float] = None,
                      bq: int = 512, bk: int = 1024):
    """Flash-style online-softmax attention in pure jnp: nested scans over
    q and kv chunks with fp32 (m, l, acc) carries. This is what the Pallas
    kernel does on TPU; the jnp version keeps the dry-run HLO honest about
    memory (no (T, S) score materialization) and compiles fast."""
    B, Tq, nq, d = q.shape
    Tk, nkv = k.shape[1], k.shape[2]
    g = nq // nkv
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    bq = min(bq, Tq)
    bk = min(bk, Tk)
    # pad to chunk multiples
    pq = (-Tq) % bq
    pk = (-Tk) % bk
    qp = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    nQ, nK = qp.shape[1] // bq, kp.shape[1] // bk

    qc = jnp.moveaxis(qp.reshape(B, nQ, bq, nkv, g, d), 1, 0)
    kc = jnp.moveaxis(kp.reshape(B, nK, bk, nkv, k.shape[-1]), 1, 0)
    vc = jnp.moveaxis(vp.reshape(B, nK, bk, nkv, v.shape[-1]), 1, 0)

    def q_step(_, qi_and_block):
        qi, qb = qi_and_block                       # qb (B, bq, nkv, g, d)
        qb = qb.astype(jnp.float32)

        def kv_step(carry, ki_and_kv):
            m, l, acc = carry
            ki, kb, vb = ki_and_kv
            s = jnp.einsum("bqhgd,bkhd->bhgqk", qb,
                           kb.astype(jnp.float32)) * scale
            iq = q_pos0 + qi * bq + jnp.arange(bq)[:, None]
            jk = ki * bk + jnp.arange(bk)[None, :]
            mask = jk < Tk
            if causal:
                mask &= iq >= jk
            if window > 0:
                mask &= (iq - jk) < window
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l = alpha * l + jnp.sum(p, axis=-1)
            acc = alpha[..., None] * acc + jnp.einsum(
                "bhgqk,bkhd->bhgqd", p, vb.astype(jnp.float32))
            return (m_new, l, acc), 0

        m0 = jnp.full((B, nkv, g, bq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, nkv, g, bq), jnp.float32)
        a0 = jnp.zeros((B, nkv, g, bq, v.shape[-1]), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0),
            (jnp.arange(nK), kc, vc))
        out = acc / jnp.maximum(l, 1e-30)[..., None]  # (B,h,g,bq,d)
        return 0, jnp.moveaxis(out, 3, 1)             # (B,bq,h,g,d)

    _, outs = jax.lax.scan(q_step, 0, (jnp.arange(nQ), qc))
    out = jnp.moveaxis(outs, 0, 1).reshape(B, nQ * bq, nq, v.shape[-1])
    return out[:, :Tq].astype(q.dtype)


def attn_partial_init(B, Tq, nkv, g, dv):
    """Fresh fp32 online-softmax carry (m, l, acc) for
    :func:`attn_core_partial` — the 'nothing attended yet' state."""
    return (jnp.full((B, nkv, g, Tq), NEG_INF, jnp.float32),
            jnp.zeros((B, nkv, g, Tq), jnp.float32),
            jnp.zeros((B, nkv, g, Tq, dv), jnp.float32))


def attn_core_partial(q, k, v, carry, *, q_pos, k_pos,
                      causal: bool = True, window: int = 0,
                      scale: Optional[float] = None,
                      bq: int = 512, bk: int = 1024):
    """One *partial* online-softmax pass over a single KV block, carrying
    (m, l, acc) across calls — the jnp oracle of
    ``kernels.flash_attention_partial`` and the per-hop core of
    :func:`seq_attn`'s ring schedule.

    q: (B, Tq, nq, d) local queries; k/v: (B, Tk, nkv, dv) one KV block;
    ``q_pos``/``k_pos``: (Tq,)/(Tk,) *global* token positions of each
    local index (striped context parallelism hands in stride-g_seq
    vectors; they may be non-monotone). The carry is the fp32
    (m, l, acc) of :func:`attn_partial_init`; chain blocks then finalize
    with :func:`attn_partial_finalize`. Internally chunked like
    :func:`attn_core_chunked`, so no (Tq, Tk) score ever materializes.
    A query row whose keys are all masked passes its carry through
    unchanged (p is zeroed under the mask — a NEG_INF running max never
    leaks exp(0) mass into l)."""
    B, Tq, nq, d = q.shape
    Tk, nkv = k.shape[1], k.shape[2]
    g = nq // nkv
    dv = v.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    m, l, acc = carry
    bq = min(bq, Tq)
    bk = min(bk, Tk)
    pq = (-Tq) % bq
    pk = (-Tk) % bk
    qp = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    qpos = jnp.pad(q_pos.astype(jnp.int32), (0, pq))
    kpos = jnp.pad(k_pos.astype(jnp.int32), (0, pk))
    kvalid = jnp.pad(jnp.ones((Tk,), bool), (0, pk))
    nQ, nK = qp.shape[1] // bq, kp.shape[1] // bk

    qc = jnp.moveaxis(qp.reshape(B, nQ, bq, nkv, g, d), 1, 0)
    kc = jnp.moveaxis(kp.reshape(B, nK, bk, nkv, d), 1, 0)
    vc = jnp.moveaxis(vp.reshape(B, nK, bk, nkv, dv), 1, 0)
    mq = jnp.moveaxis(jnp.pad(m, ((0, 0),) * 3 + ((0, pq),),
                              constant_values=NEG_INF
                              ).reshape(B, nkv, g, nQ, bq), 3, 0)
    lq = jnp.moveaxis(jnp.pad(l, ((0, 0),) * 3 + ((0, pq),)
                              ).reshape(B, nkv, g, nQ, bq), 3, 0)
    aq = jnp.moveaxis(jnp.pad(acc, ((0, 0),) * 3 + ((0, pq), (0, 0))
                              ).reshape(B, nkv, g, nQ, bq, dv), 3, 0)

    def q_step(_, xs):
        qb, qpb, m0, l0, a0 = xs                # qb (B, bq, nkv, g, d)
        qb = qb.astype(jnp.float32)

        def kv_step(cr, ys):
            mc, lc, ac = cr
            kb, vb, kpb, kvb = ys
            s = jnp.einsum("bqhgd,bkhd->bhgqk", qb,
                           kb.astype(jnp.float32),
                           preferred_element_type=jnp.float32) * scale
            mask = kvb[None, :]
            iq = qpb[:, None]
            jk = kpb[None, :]
            if causal:
                mask &= iq >= jk
            if window > 0:
                mask &= (iq - jk) < window
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(mc, jnp.max(s, axis=-1))
            # the explicit mask keeps exp(0) out of l when a row is still
            # fully masked (m_new == NEG_INF, s - m_new == 0)
            p = jnp.where(mask[None, None, None],
                          jnp.exp(s - m_new[..., None]), 0.0)
            alpha = jnp.exp(mc - m_new)
            lc = alpha * lc + jnp.sum(p, axis=-1)
            ac = alpha[..., None] * ac + jnp.einsum(
                "bhgqk,bkhd->bhgqd", p, vb.astype(jnp.float32),
                preferred_element_type=jnp.float32)
            return (m_new, lc, ac), 0

        (m1, l1, a1), _ = jax.lax.scan(kv_step, (m0, l0, a0),
                                       (kc, vc, kpos.reshape(nK, bk),
                                        kvalid.reshape(nK, bk)))
        return 0, (m1, l1, a1)

    _, (mo, lo, ao) = jax.lax.scan(
        q_step, 0, (qc, qpos.reshape(nQ, bq), mq, lq, aq))
    m = jnp.moveaxis(mo, 0, 3).reshape(B, nkv, g, nQ * bq)[..., :Tq]
    l = jnp.moveaxis(lo, 0, 3).reshape(B, nkv, g, nQ * bq)[..., :Tq]
    acc = jnp.moveaxis(ao, 0, 3).reshape(B, nkv, g, nQ * bq, dv
                                         )[..., :Tq, :]
    return m, l, acc


def attn_partial_finalize(carry, dtype):
    """Normalize a chained (m, l, acc) carry into the (B, Tq, nq, dv)
    attention output."""
    m, l, acc = carry
    out = acc / jnp.maximum(l, 1e-30)[..., None]    # (B, nkv, g, Tq, dv)
    B, nkv, g, Tq, dv = out.shape
    return jnp.moveaxis(out, 3, 1).reshape(B, Tq, nkv * g, dv
                                           ).astype(dtype)


def paged_attn_core(q, k, v, *, q_pos, q_len, window: int = 0,
                    scale: Optional[float] = None):
    """Variable-length attention over per-slot KV gathered from page pools.

    q: (R, T, nq, d) — R request slots, T rows (1 for pure decode, the
    chunk length for chunked prefill); k/v: (R, S, nkv, dv) — slot r's
    pages gathered in page-table order, so key index j IS global position
    j; q_pos: (R, T) int32 global query positions; q_len: (R,) int32
    valid query rows per slot (rows >= q_len[r] are chunk padding or idle
    slots and are fully masked).

    This is the jnp oracle the model calls in ``mode='paged'``;
    ``kernels.flash_attention_paged`` mirrors it page-by-page and is
    validated against it. Two properties the serving tests pin:

      * masked scores contribute *exactly* zero (explicit ``where`` on p),
        so stale data in freed/reused pages and the reserved null page
        never leak probability mass into live rows;
      * the reduction runs over the FIXED gathered length S in one fp32
        softmax, so every chunking of the same prompt reduces the same
        score vector per row — chunked prefill equals one-shot prefill
        bitwise (tests/test_serving.py).

    A fully-masked row (idle slot) yields a finite garbage output that the
    engine discards via q_len."""
    R, T, nq, d = q.shape
    S, nkv = k.shape[1], k.shape[2]
    g = nq // nkv
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    qg = q.reshape(R, T, nkv, g, d)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32),
                   k.astype(jnp.float32),
                   preferred_element_type=jnp.float32) * scale
    iq = q_pos.astype(jnp.int32)[:, :, None]            # (R, T, 1)
    jk = jnp.arange(S, dtype=jnp.int32)[None, None, :]  # (1, 1, S)
    row = jnp.arange(T, dtype=jnp.int32)[None, :, None]
    mask = (row < q_len.astype(jnp.int32)[:, None, None]) & (iq >= jk)
    if window > 0:
        mask &= (iq - jk) < window
    mask = mask[:, None, None]                          # (R, 1, 1, T, S)
    s = jnp.where(mask, s, NEG_INF)
    m = jnp.max(s, axis=-1)
    p = jnp.where(mask, jnp.exp(s - m[..., None]), 0.0)
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bhgqd", p, v.astype(jnp.float32),
                   preferred_element_type=jnp.float32)
    o = o / jnp.maximum(l, 1e-30)[..., None]
    return jnp.moveaxis(o, 3, 1).reshape(R, T, nq, v.shape[-1]
                                         ).astype(q.dtype)


def seq_attn(q, k, v, axes: M.MeshAxes, *, causal: bool = True,
             window: int = 0):
    """Context-parallel causal attention over the ``seq`` mesh axis.

    Runs inside shard_map on the striped layout (seq-rank r holds global
    positions r, r + p, r + 2p, ... — ``mesh.stripe_seq``; each rank's
    causal work is balanced because its stripe spans the whole sequence).
    Two schedules, identical results up to fp32 reassociation:

      * blocking (``overlap.ring_attention`` off): one KV all-gather
        over ``seq``, one partial pass with the gathered (non-monotone)
        position vector;
      * ring (on): p-1 ``ppermute`` hops circulate the KV shards —
        after s hops this rank holds seq-rank (r - s) mod p's block
        (``mesh.ring_perm``) — with hop s+1's permute issued BEFORE hop
        s's partial attention, so the exchange hides under attention
        compute exactly like the PR-1/2 ring-GEMM schedule.

    Cross-hop accumulation is the fp32 (m, l, acc) online-softmax carry
    of :func:`attn_core_partial`. p == 1 degenerates to the plain
    :func:`attn_core` call, bit for bit."""
    p = axes.gseq
    if p <= 1:
        return attn_core(q, k, v, causal=causal, window=window)
    B, C, nq, d = q.shape
    nkv, dv = k.shape[2], v.shape[-1]
    r = M.axis_index(axes.seq)
    q_pos = jnp.arange(C, dtype=jnp.int32) * p + r
    # the carry meets per-seq-rank scores inside the scans, so it must
    # enter them already varying over seq (shard_map's vma check)
    carry = jax.tree.map(
        lambda c: jax.lax.pcast(c, axes.seq, to="varying"),
        attn_partial_init(B, C, nkv, nq // nkv, dv))
    if not axes.overlap.ring_attention:
        kg = M.all_gather(k, axes.seq, dim=1)
        vg = M.all_gather(v, axes.seq, dim=1)
        # gathered index rho*C + j holds global position j*p + rho
        i = jnp.arange(p * C, dtype=jnp.int32)
        k_pos = (i % C) * p + i // C
        carry = attn_core_partial(q, kg, vg, carry, q_pos=q_pos,
                                  k_pos=k_pos, causal=causal,
                                  window=window)
        return attn_partial_finalize(carry, q.dtype)
    cur_k, cur_v = k, v
    local = jnp.arange(C, dtype=jnp.int32) * p
    for s in range(p):
        with trace.scope("ring_exchange", axes.seq, f"hop{s}"):
            if s < p - 1:
                # prefetch: hop s+1's KV permutes while hop s computes
                # (the permute has no data dependency on this hop's
                # partials, so the latency-hiding scheduler overlaps them)
                nxt_k = M.ppermute_ring(cur_k, axes.seq)
                nxt_v = M.ppermute_ring(cur_v, axes.seq)
            owner = (r - s) % p
            carry = attn_core_partial(q, cur_k, cur_v, carry, q_pos=q_pos,
                                      k_pos=local + owner, causal=causal,
                                      window=window)
            if s < p - 1:
                cur_k, cur_v = nxt_k, nxt_v
    return attn_partial_finalize(carry, q.dtype)


def decode_core_seqsharded(q, k, v, pos, axes, *, window: int = 0,
                           scale: Optional[float] = None):
    """Single-token decode against a KV cache whose *sequence* dim is
    sharded over the data axis. Partial softmax per shard, merged with a
    log-sum-exp psum over ``data``.

    q: (B, 1, nq, d); k/v: (B, S_local, nkv, d); pos: scalar absolute
    position of the query token (cache entries > pos are masked)."""
    B, _, nq, d = q.shape
    S_local, nkv = k.shape[1], k.shape[2]
    g = nq // nkv
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    shard = M.axis_index(axes.data)
    jk = shard * S_local + jnp.arange(S_local)  # global cache positions
    qg = q.reshape(B, nkv, g, d)
    scores = jnp.einsum("bhgd,bkhd->bhgk", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    ok = jk <= pos
    if window > 0:
        ok &= (pos - jk) < window
    scores = jnp.where(ok[None, None, None, :], scores, NEG_INF)
    m_local = jnp.max(scores, axis=-1)
    m = M.pmax(m_local, axes.data)
    e = jnp.exp(scores - m[..., None])
    num = jnp.einsum("bhgk,bkhd->bhgd", e, v.astype(jnp.float32))
    den = jnp.sum(e, axis=-1)
    num = M.psum(num, axes.data)
    den = M.psum(den, axes.data)
    out = num / den[..., None]
    return out.reshape(B, 1, nq, d).astype(q.dtype)


# ---------------------------------------------------------------------- #
# GQA attention layer
# ---------------------------------------------------------------------- #

def kv_layout(cfg, axes: M.MeshAxes):
    """(nq_local, nkv_local, duplicated?). When G_y > n_kv_heads (e.g. the
    16-way 1D baseline on a kv=8 GQA arch), KV heads are *duplicated*
    across y ranks — Megatron's standard GQA-under-wide-TP treatment."""
    nq_l = cfg.n_heads // axes.gy
    if cfg.n_kv_heads % axes.gy == 0:
        return nq_l, cfg.n_kv_heads // axes.gy, False
    if axes.gy % cfg.n_kv_heads or cfg.n_heads % axes.gy:
        raise ValueError(f"{cfg.name}: cannot lay out {cfg.n_kv_heads} kv "
                         f"heads on G_y={axes.gy}")
    return nq_l, 1, True


def attn_init(key, cfg, axes: M.MeshAxes, *, dtype=jnp.bfloat16,
              stack=(), abstract=False, cross: bool = False):
    hd = cfg.head_dim or cfg.d_model // cfg.n_heads
    nq, nkv = cfg.n_heads, cfg.n_kv_heads
    _, _, dup = kv_layout(cfg, axes)
    keys = jax.random.split(key, 4)
    p = {}
    if dup and not cross:
        assert not getattr(cfg, "attn_bias", False), \
            "bias unsupported in duplicated-KV layout"
        p["wq"] = PP.tp_linear_init(keys[0], cfg.d_model, nq * hd, axes,
                                    dtype=dtype, stack=stack,
                                    abstract=abstract)
        # full (small) kv projection, replicated over y; each rank slices
        # its duplicated head. Grads need a y psum (y_reduce).
        wkv = PP.tp_linear_init(keys[1], cfg.d_model, 2 * nkv * hd, axes,
                                in_shard="x", out_shard=None, dtype=dtype,
                                stack=stack, abstract=abstract)
        wkv.y_reduce = True
        p["wkv_dup"] = wkv
        p["wo"] = PP.tp_linear_init(keys[2], nq * hd, cfg.d_model, axes,
                                    in_shard="y", out_shard="x",
                                    dtype=dtype, stack=stack,
                                    abstract=abstract)
        if getattr(cfg, "qk_norm", False):
            spec = P(*([None] * (len(stack) + 1)))
            def mk():
                if abstract:
                    return Boxed(jax.ShapeDtypeStruct((*stack, hd), dtype),
                                 spec)
                return Boxed(jnp.ones((*stack, hd), dtype), spec)
            p["q_norm"], p["k_norm"] = mk(), mk()
        return p
    if cross:
        # q from decoder stream; kv from encoder states
        p["wq"] = PP.tp_linear_init(keys[0], cfg.d_model, nq * hd, axes,
                                    dtype=dtype, stack=stack,
                                    abstract=abstract)
        p["wk"] = PP.tp_linear_init(keys[1], cfg.d_model, nkv * hd,
                                     axes, dtype=dtype, stack=stack,
                                     abstract=abstract)
        p["wv"] = PP.tp_linear_init(keys[3], cfg.d_model, nkv * hd,
                                    axes, dtype=dtype, stack=stack,
                                    abstract=abstract)
    else:
        # separate q/k/v weights: a fused (nq+2nkv)*hd matrix column-
        # sharded over y would change its *global* layout meaning with
        # G_y (per-shard [q|k|v] chunks) — mesh-dependent semantics.
        p["wq"] = PP.tp_linear_init(keys[0], cfg.d_model, nq * hd, axes,
                                    dtype=dtype, stack=stack,
                                    abstract=abstract)
        p["wk"] = PP.tp_linear_init(keys[1], cfg.d_model, nkv * hd, axes,
                                    dtype=dtype, stack=stack,
                                    abstract=abstract)
        p["wv"] = PP.tp_linear_init(keys[3], cfg.d_model, nkv * hd, axes,
                                    dtype=dtype, stack=stack,
                                    abstract=abstract)
    p["wo"] = PP.tp_linear_init(keys[2], nq * hd, cfg.d_model, axes,
                                in_shard="y", out_shard="x", dtype=dtype,
                                stack=stack, abstract=abstract)
    if getattr(cfg, "attn_bias", False):
        p["bq"] = PP.tp_bias_init(nq * hd, axes, dtype=dtype,
                                  stack=stack, abstract=abstract)
        if not cross:
            p["bk"] = PP.tp_bias_init(nkv * hd, axes, dtype=dtype,
                                      stack=stack, abstract=abstract)
            p["bv"] = PP.tp_bias_init(nkv * hd, axes, dtype=dtype,
                                      stack=stack, abstract=abstract)
        p["bo"] = PP.tp_bias_init(cfg.d_model, axes, out_shard="x",
                                  dtype=dtype, stack=stack,
                                  abstract=abstract)
    if getattr(cfg, "qk_norm", False):
        spec = P(*([None] * (len(stack) + 1)))
        def mk():
            if abstract:
                return Boxed(jax.ShapeDtypeStruct((*stack, hd), dtype), spec)
            return Boxed(jnp.ones((*stack, hd), dtype), spec)
        p["q_norm"], p["k_norm"] = mk(), mk()
    return p


def _split_qkv(qkv, nq_l, nkv_l, hd):
    B, T = qkv.shape[:2]
    q, k, v = jnp.split(qkv, [nq_l * hd, (nq_l + nkv_l) * hd], axis=-1)
    return (q.reshape(B, T, nq_l, hd), k.reshape(B, T, nkv_l, hd),
            v.reshape(B, T, nkv_l, hd))


def attn_apply(p, h, cfg, axes: M.MeshAxes, *, positions, mode="train",
               cache=None, window: int = 0, causal: bool = True,
               paged=None):
    """Returns (out, new_cache).

    mode: 'train' (no cache), 'prefill' (build cache), 'decode' (T==1,
    read+update cache), 'decode_seqshard' (cache seq-sharded over data),
    'paged' (continuous-batching serving: per-slot rows at per-slot
    positions against a pooled paged KV cache; ``paged`` carries
    ``{"table": (R, max_pages) int32, "q_len": (R,) int32}``, see
    docs/serving.md).
    """
    hd = cfg.head_dim or cfg.d_model // cfg.n_heads
    nq_l, nkv_l, dup = kv_layout(cfg, axes)
    if dup:
        B, T = h.shape[:2]
        q = PP.tp_matmul(h, p["wq"], axes, "x", "y")
        q = q.reshape(B, T, nq_l, hd)
        kv = PP.tp_matmul(h, p["wkv_dup"], axes, "x", None)
        kv = kv.reshape(B, T, 2, cfg.n_kv_heads, hd)
        # this rank's duplicated head: kv head j serves q heads [j*g, ...)
        head = (M.axis_index(axes.y) * cfg.n_kv_heads) // axes.gy
        kv = jax.lax.dynamic_slice_in_dim(kv, head, 1, axis=3)
        k, v = kv[:, :, 0], kv[:, :, 1]        # (B, T, 1, hd)
    else:
        B, T = h.shape[:2]
        q = PP.tp_matmul(h, p["wq"], axes, "x", "y")
        k = PP.tp_matmul(h, p["wk"], axes, "x", "y")
        v = PP.tp_matmul(h, p["wv"], axes, "x", "y")
        if "bq" in p:
            q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
        q = q.reshape(B, T, nq_l, hd)
        k = k.reshape(B, T, nkv_l, hd)
        v = v.reshape(B, T, nkv_l, hd)
    if "q_norm" in p:
        q = _plain_rms(q, p["q_norm"])
        k = _plain_rms(k, p["k_norm"])
    if cfg.rotary_pct > 0:
        q = apply_rope(q, positions, cfg.rope_theta, cfg.rotary_pct)
        k_pos = positions
        k = apply_rope(k, k_pos, cfg.rope_theta, cfg.rotary_pct)

    new_cache = cache
    if mode in ("train", "prefill"):
        if mode == "train" and axes.gseq > 1:
            # context parallelism: ring/blocking partial attention over
            # the striped seq shards (positions already carry the stripe)
            with trace.layer("attn_core"):
                out = seq_attn(q, k, v, axes, causal=causal,
                               window=window)
        else:
            with trace.layer("attn_core"):
                out = attn_core(q, k, v, causal=causal, window=window)
        if mode == "prefill":
            kc, vc = cache["k"], cache["v"]
            kc = jax.lax.dynamic_update_slice(kc, k.astype(kc.dtype),
                                              (0, 0, 0, 0))
            vc = jax.lax.dynamic_update_slice(vc, v.astype(vc.dtype),
                                              (0, 0, 0, 0))
            new_cache = {"k": kc, "v": vc}
    elif mode == "decode":
        pos = positions[:, 0]  # (B,)
        kc, vc = cache["k"], cache["v"]
        idx = pos[0]  # uniform position across batch (standard batch decode)
        kc = jax.lax.dynamic_update_slice(kc, k.astype(kc.dtype),
                                          (0, idx, 0, 0))
        vc = jax.lax.dynamic_update_slice(vc, v.astype(vc.dtype),
                                          (0, idx, 0, 0))
        new_cache = {"k": kc, "v": vc}
        S = kc.shape[1]
        jk = jnp.arange(S)
        ok = jk <= idx
        if window > 0:
            ok &= (idx - jk) < window
        with trace.layer("attn_core"):
            out = _decode_attn(q, kc, vc, ok)
    elif mode == "paged":
        # continuous-batching serving (docs/serving.md): the cache is a
        # physical page pool (P_local, page, H_local, hd); each slot's
        # logical sequence lives wherever its page table says. Rows are
        # per-slot chunk tokens (prefill) or single decode tokens at
        # per-slot global positions — no uniform-position assumption.
        kp, vp = cache["k"], cache["v"]
        page = kp.shape[1]
        table = paged["table"].astype(jnp.int32)        # (R, max_pages)
        q_len = paged["q_len"].astype(jnp.int32)        # (R,)
        R, Tr = positions.shape
        valid = jnp.arange(Tr, dtype=jnp.int32)[None, :] < q_len[:, None]
        slot_pages = jnp.clip(positions.astype(jnp.int32) // page, 0,
                              table.shape[1] - 1)
        pid = jnp.take_along_axis(table, slot_pages, axis=1)
        # invalid rows (chunk padding / idle slots) collapse onto the
        # reserved null page 0 at offset 0 — written, never read (the
        # allocator never hands out page 0 and masked rows zero p)
        pid = jnp.where(valid, pid, 0)
        off = jnp.where(valid, positions.astype(jnp.int32) % page, 0)
        kp = kp.at[pid, off].set(k.astype(kp.dtype))
        vp = vp.at[pid, off].set(v.astype(vp.dtype))
        new_cache = {"k": kp, "v": vp}
        # gather each slot's pages in table order: key index j of the
        # gathered (R, S_max, ...) view IS global position j
        kc = kp[table].reshape(R, -1, *kp.shape[2:])
        vc = vp[table].reshape(R, -1, *vp.shape[2:])
        with trace.layer("attn_core"):
            out = paged_attn_core(q, kc, vc, q_pos=positions, q_len=q_len,
                                  window=window)
    elif mode == "decode_seqshard":
        # global_batch=1 long-context: cache seq dim sharded over data; the
        # fresh token's kv is written by the owning shard only.
        pos = positions[0, 0]
        kc, vc = cache["k"], cache["v"]
        S_local = kc.shape[1]
        shard = M.axis_index(axes.data)
        local_idx = pos - shard * S_local
        owns = (local_idx >= 0) & (local_idx < S_local)
        safe = jnp.clip(local_idx, 0, S_local - 1)
        kw = jnp.where(owns, k.astype(kc.dtype),
                       jax.lax.dynamic_slice(kc, (0, safe, 0, 0),
                                             k.shape))
        vw = jnp.where(owns, v.astype(vc.dtype),
                       jax.lax.dynamic_slice(vc, (0, safe, 0, 0), v.shape))
        kc = jax.lax.dynamic_update_slice(kc, kw, (0, safe, 0, 0))
        vc = jax.lax.dynamic_update_slice(vc, vw, (0, safe, 0, 0))
        new_cache = {"k": kc, "v": vc}
        with trace.layer("attn_core"):
            out = decode_core_seqsharded(q, kc, vc, pos, axes,
                                         window=window)
    else:
        raise ValueError(mode)

    B, T = out.shape[:2]
    o = PP.tp_matmul(out.reshape(B, T, nq_l * hd), p["wo"], axes, "y", "x")
    if "bo" in p:
        o = o + p["bo"]
    return o, new_cache


def _decode_attn(q, kc, vc, ok):
    B, _, nq, d = q.shape
    nkv = kc.shape[2]
    g = nq // nkv
    scores = jnp.einsum("bhgd,bkhd->bhgk",
                        q.reshape(B, nkv, g, d).astype(jnp.float32),
                        kc.astype(jnp.float32)) / math.sqrt(d)
    scores = jnp.where(ok[None, None, None, :], scores, NEG_INF)
    probs = _softmax_fp32(scores)
    out = jnp.einsum("bhgk,bkhd->bhgd", probs, vc.astype(jnp.float32))
    return out.reshape(B, 1, nq, d).astype(q.dtype)


def attn_cache_spec(cfg, axes: M.MeshAxes, batch_global, seq, *,
                    dtype=jnp.bfloat16, seqshard: bool = False):
    """GLOBAL ShapeDtypeStructs + PartitionSpecs for this layer's KV cache.

    In the duplicated-KV layout the cache's global head dim is G_y (one
    duplicated head per y rank)."""
    hd = cfg.head_dim or cfg.d_model // cfg.n_heads
    _, nkv_l, dup = kv_layout(cfg, axes)
    heads_global = axes.gy if dup else cfg.n_kv_heads
    if seqshard:
        spec = axes.pspec(None, axes.data, axes.y, None)
    else:
        spec = axes.pspec(axes.batch_axes(), None, axes.y, None)
    shape = (batch_global, seq, heads_global, hd)
    return {"k": (jax.ShapeDtypeStruct(shape, dtype), spec),
            "v": (jax.ShapeDtypeStruct(shape, dtype), spec)}


def paged_attn_cache_spec(cfg, axes: M.MeshAxes, n_pages_global, page_size,
                          *, dtype=jnp.bfloat16):
    """GLOBAL (struct, spec) for this layer's paged KV pool.

    Shape (n_pages_global, page_size, heads_global, hd): physical pages
    shard over the batch axes (data x z, the same rule as the dense decode
    cache — z co-shards batch storage per the paper), KV heads over y,
    replicated over x (x shards the residual stream, not the cache). Each
    batch shard owns n_pages_global / (g_data*g_z) contiguous pages whose
    page tables hold shard-LOCAL ids; page 0 of every shard is the
    reserved null page (docs/serving.md)."""
    hd = cfg.head_dim or cfg.d_model // cfg.n_heads
    _, _, dup = kv_layout(cfg, axes)
    heads_global = axes.gy if dup else cfg.n_kv_heads
    spec = axes.pspec(axes.batch_axes(), None, axes.y, None)
    shape = (n_pages_global, page_size, heads_global, hd)
    return {"k": (jax.ShapeDtypeStruct(shape, dtype), spec),
            "v": (jax.ShapeDtypeStruct(shape, dtype), spec)}


# ---------------------------------------------------------------------- #
# cross attention (whisper decoder)
# ---------------------------------------------------------------------- #

def cross_attn_apply(p, h, enc_kv, cfg, axes: M.MeshAxes):
    """enc_kv: precomputed (k, v) from encoder states, (B, S_enc, nkv_l, hd)."""
    hd = cfg.head_dim or cfg.d_model // cfg.n_heads
    nq_l = cfg.n_heads // axes.gy
    B, T = h.shape[:2]
    q = PP.tp_matmul(h, p["wq"], axes, "x", "y").reshape(B, T, nq_l, hd)
    k, v = enc_kv
    out = attn_core(q, k, v, causal=False)
    o = PP.tp_matmul(out.reshape(B, T, nq_l * hd), p["wo"], axes, "y", "x")
    if "bo" in p:
        o = o + p["bo"]
    return o


def cross_attn_kv(p, enc_states, cfg, axes: M.MeshAxes):
    hd = cfg.head_dim or cfg.d_model // cfg.n_heads
    nkv_l = cfg.n_kv_heads // axes.gy
    B, S = enc_states.shape[:2]
    k = PP.tp_matmul(enc_states, p["wk"], axes, "x", "y")
    v = PP.tp_matmul(enc_states, p["wv"], axes, "x", "y")
    return (k.reshape(B, S, nkv_l, hd), v.reshape(B, S, nkv_l, hd))


# ---------------------------------------------------------------------- #
# DeepSeek Multi-head Latent Attention (MLA)
# ---------------------------------------------------------------------- #

def mla_init(key, cfg, axes: M.MeshAxes, *, dtype=jnp.bfloat16, stack=(),
             abstract=False):
    m = cfg.mla
    nq = cfg.n_heads
    qk_dim = m.qk_nope_dim + m.qk_rope_dim
    ks = jax.random.split(key, 8)
    from jax.sharding import PartitionSpec as P

    def rep_norm(dim):
        spec = P(*([None] * (len(stack) + 1)))
        if abstract:
            return Boxed(jax.ShapeDtypeStruct((*stack, dim), dtype), spec)
        return Boxed(jnp.ones((*stack, dim), dtype), spec)

    p = {}
    if m.q_lora_rank:
        p["w_dq"] = PP.tp_linear_init(ks[0], cfg.d_model, m.q_lora_rank,
                                      axes, in_shard="x", out_shard=None,
                                      dtype=dtype, stack=stack,
                                      abstract=abstract)
        p["q_norm"] = rep_norm(m.q_lora_rank)
        p["w_uq"] = PP.tp_linear_init(ks[1], m.q_lora_rank, nq * qk_dim,
                                      axes, in_shard=None, out_shard="y",
                                      dtype=dtype, stack=stack,
                                      abstract=abstract)
    else:
        p["w_q"] = PP.tp_linear_init(ks[1], cfg.d_model, nq * qk_dim, axes,
                                     dtype=dtype, stack=stack,
                                     abstract=abstract)
    p["w_dkv"] = PP.tp_linear_init(
        ks[2], cfg.d_model, m.kv_lora_rank + m.qk_rope_dim, axes,
        in_shard="x", out_shard=None, dtype=dtype, stack=stack,
        abstract=abstract)
    p["kv_norm"] = rep_norm(m.kv_lora_rank)
    p["w_uk"] = PP.tp_linear_init(ks[3], m.kv_lora_rank, nq * m.qk_nope_dim,
                                  axes, in_shard=None, out_shard="y",
                                  dtype=dtype, stack=stack,
                                  abstract=abstract)
    p["w_uv"] = PP.tp_linear_init(ks[4], m.kv_lora_rank, nq * m.v_dim, axes,
                                  in_shard=None, out_shard="y", dtype=dtype,
                                  stack=stack, abstract=abstract)
    p["wo"] = PP.tp_linear_init(ks[5], nq * m.v_dim, cfg.d_model, axes,
                                in_shard="y", out_shard="x", dtype=dtype,
                                stack=stack, abstract=abstract)
    return p


def _mla_q(p, h, cfg, axes, positions):
    m = cfg.mla
    nq_l = cfg.n_heads // axes.gy
    qk_dim = m.qk_nope_dim + m.qk_rope_dim
    B, T = h.shape[:2]
    if "w_dq" in p:
        cq = PP.tp_matmul(h, p["w_dq"], axes, "x", None)
        cq = _plain_rms(cq, p["q_norm"])
        q = PP.tp_matmul(cq, p["w_uq"], axes, None, "y")
    else:
        q = PP.tp_matmul(h, p["w_q"], axes, "x", "y")
    q = q.reshape(B, T, nq_l, qk_dim)
    q_nope, q_rope = q[..., :m.qk_nope_dim], q[..., m.qk_nope_dim:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta,
                        yarn=cfg.rope_scaling)
    return q_nope, q_rope


def mla_scale(cfg) -> float:
    """MLA's softmax scale: 1/sqrt(qk head dim), times YaRN's
    ``mscale_all_dim`` temperature squared where the config stretches
    the context (DeepSeek-V2)."""
    m = cfg.mla
    scale = 1.0 / math.sqrt(m.qk_nope_dim + m.qk_rope_dim)
    y = cfg.rope_scaling
    if y is not None and y.mscale_all_dim:
        scale *= yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return scale


def mla_apply(p, h, cfg, axes: M.MeshAxes, *, positions, mode="train",
              cache=None):
    """MLA forward. train/prefill: materialized per-head K/V; decode:
    absorbed matmuls against the compressed (c_kv, k_rope) cache."""
    m = cfg.mla
    nq_l = cfg.n_heads // axes.gy
    B, T = h.shape[:2]
    scale = mla_scale(cfg)

    dkv = PP.tp_matmul(h, p["w_dkv"], axes, "x", None)
    ckv, k_rope = dkv[..., :m.kv_lora_rank], dkv[..., m.kv_lora_rank:]
    ckv = _plain_rms(ckv, p["kv_norm"])
    k_rope = apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta,
                        yarn=cfg.rope_scaling)  # (B,T,1,rope)
    q_nope, q_rope = _mla_q(p, h, cfg, axes, positions)

    new_cache = cache
    if mode in ("train", "prefill"):
        k_nope = PP.tp_matmul(ckv, p["w_uk"], axes, None, "y")
        k_nope = k_nope.reshape(B, T, nq_l, m.qk_nope_dim)
        v = PP.tp_matmul(ckv, p["w_uv"], axes, None, "y")
        v = v.reshape(B, T, nq_l, m.v_dim)
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope, (B, T, nq_l, m.qk_rope_dim))],
            axis=-1)
        with trace.layer("attn_core"):
            out = attn_core(q, k, v, causal=True, scale=scale)
        if mode == "prefill":
            cc = jax.lax.dynamic_update_slice(
                cache["ckv"], ckv.astype(cache["ckv"].dtype), (0, 0, 0))
            rc = jax.lax.dynamic_update_slice(
                cache["k_rope"], k_rope[:, :, 0, :].astype(
                    cache["k_rope"].dtype), (0, 0, 0))
            new_cache = {"ckv": cc, "k_rope": rc}
    elif mode == "decode":
        idx = positions[0, 0]
        cc = jax.lax.dynamic_update_slice(
            cache["ckv"], ckv.astype(cache["ckv"].dtype), (0, idx, 0))
        rc = jax.lax.dynamic_update_slice(
            cache["k_rope"], k_rope[:, :, 0, :].astype(
                cache["k_rope"].dtype), (0, idx, 0))
        new_cache = {"ckv": cc, "k_rope": rc}
        # absorbed: q_eff = q_nope @ W_uk  -> score against compressed cache
        wuk = M.all_gather(p["w_uk"], axes.z, dim=1)
        wuk = wuk.reshape(m.kv_lora_rank, nq_l, m.qk_nope_dim)
        q_eff = jnp.einsum("bthd,rhd->bthr", q_nope.astype(jnp.float32),
                           wuk.astype(jnp.float32))  # (B,1,nq_l,rank)
        S = cc.shape[1]
        scores = (jnp.einsum("bthr,bsr->bths", q_eff,
                             cc.astype(jnp.float32))
                  + jnp.einsum("bthd,bsd->bths",
                               q_rope.astype(jnp.float32),
                               rc.astype(jnp.float32))) * scale
        ok = jnp.arange(S) <= idx
        scores = jnp.where(ok[None, None, None, :], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        ctx = jnp.einsum("bths,bsr->bthr", probs, cc.astype(jnp.float32))
        wuv = M.all_gather(p["w_uv"], axes.z, dim=1)
        wuv = wuv.reshape(m.kv_lora_rank, nq_l, m.v_dim)
        out = jnp.einsum("bthr,rhd->bthd", ctx, wuv.astype(jnp.float32)
                         ).astype(h.dtype)
    else:
        raise ValueError(mode)

    o = PP.tp_matmul(out.reshape(B, T, nq_l * m.v_dim), p["wo"], axes,
                     "y", "x")
    return o, new_cache


def mla_cache_spec(cfg, axes: M.MeshAxes, batch_global, seq, *,
                   dtype=jnp.bfloat16):
    m = cfg.mla
    bspec = axes.pspec(axes.batch_axes(), None, None)
    return {
        "ckv": (jax.ShapeDtypeStruct((batch_global, seq, m.kv_lora_rank),
                                     dtype), bspec),
        "k_rope": (jax.ShapeDtypeStruct((batch_global, seq, m.qk_rope_dim),
                                        dtype), bspec),
    }
