"""Generic decoder-only LM assembled from 4D-parallel layers.

Layers are executed with ``lax.scan`` over the architecture's repeating
period (params stacked over periods) so HLO size / compile time stays flat
in depth — 61-layer DeepSeek-V3 compiles the same program as a 2-layer
smoke model. Heterogeneous patterns (jamba's mamba/attn interleave, MoE
every-other-layer, xLSTM's 7:1) unroll the period *inside* the scan body.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import mesh as M
from repro.core import parallel as PP
from repro.core import trace
from repro.core.partition import Boxed
from repro.layers import attention as A
from repro.layers import mamba as MB
from repro.layers import mlp as FF
from repro.layers import moe as MOE
from repro.layers import xlstm as XL
from repro.models.base import ArchConfig


# ---------------------------------------------------------------------- #
# init
# ---------------------------------------------------------------------- #

def _norm_init(cfg, axes, dtype, stack, abstract):
    if cfg.norm == "layernorm":
        return {"g": PP.norm_param_init(cfg.d_model, axes, dtype=dtype,
                                        stack=stack, abstract=abstract),
                "b": PP.norm_param_init(cfg.d_model, axes, dtype=dtype,
                                        value=0.0, stack=stack,
                                        abstract=abstract)}
    return {"g": PP.norm_param_init(cfg.d_model, axes, dtype=dtype,
                                    stack=stack, abstract=abstract)}


def _apply_norm(p, h, cfg, axes):
    with trace.layer("norm"):
        if cfg.norm == "layernorm":
            return PP.layer_norm(h, p["g"], p["b"], axes, cfg.d_model)
        return PP.rms_norm(h, p["g"], axes, cfg.d_model)


def _mixer_init(kind, key, cfg, axes, dtype, stack, abstract):
    if kind == "attn":
        return A.attn_init(key, cfg, axes, dtype=dtype, stack=stack,
                           abstract=abstract)
    if kind == "mla":
        return A.mla_init(key, cfg, axes, dtype=dtype, stack=stack,
                          abstract=abstract)
    if kind == "mamba":
        return MB.mamba_init(key, cfg, axes, dtype=dtype, stack=stack,
                             abstract=abstract)
    if kind == "mlstm":
        return XL.mlstm_init(key, cfg, axes, dtype=dtype, stack=stack,
                             abstract=abstract)
    if kind == "slstm":
        return XL.slstm_init(key, cfg, axes, dtype=dtype, stack=stack,
                             abstract=abstract)
    raise ValueError(kind)


def _ffn_init(kind, key, cfg, axes, dtype, stack, abstract):
    if kind == "mlp":
        return FF.mlp_init(key, cfg.d_model, cfg.d_ff, cfg.act, axes,
                           gated=cfg.gated_mlp, bias=cfg.mlp_bias,
                           dtype=dtype, stack=stack, abstract=abstract)
    if kind == "moe":
        return MOE.moe_init(key, cfg, axes, dtype=dtype, stack=stack,
                            abstract=abstract)
    return None


def decoder_init(key, cfg: ArchConfig, axes: M.MeshAxes, *,
                 dtype=jnp.bfloat16, abstract: bool = False
                 ) -> Dict[str, Any]:
    cfg.validate_axes(axes)
    segs = cfg.segments()
    keys = jax.random.split(key, 4 + 2 * sum(len(k) for k, _ in segs))
    ki = 4

    segments = {}
    for s, (kinds, n_periods) in enumerate(segs):
        stack = (n_periods,)
        blocks = {}
        for i, (mixer, ffn) in enumerate(kinds):
            blk = {"norm1": _norm_init(cfg, axes, dtype, stack, abstract),
                   "mixer": _mixer_init(mixer, keys[ki], cfg, axes,
                                        dtype, stack, abstract)}
            ki += 1
            if ffn != "none":
                blk["norm2"] = _norm_init(cfg, axes, dtype, stack, abstract)
                blk["ffn"] = _ffn_init(ffn, keys[ki], cfg, axes, dtype,
                                       stack, abstract)
            ki += 1
            blocks[f"pos{i}"] = blk
        segments[f"seg{s}"] = blocks

    params = {
        "embed": PP.embedding_init(keys[0], cfg.padded_vocab, cfg.d_model,
                                   axes, dtype=dtype, abstract=abstract),
        "segments": segments,
        "final_norm": _norm_init(cfg, axes, dtype, (), abstract),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = PP.tp_linear_init(
            keys[1], cfg.d_model, cfg.padded_vocab, axes, dtype=dtype,
            scale=0.02, abstract=abstract)
    if cfg.mtp_depth > 0:
        # DeepSeek-V3 multi-token prediction (depth 1): combine the main
        # stream with the next token's embedding, run one extra block,
        # predict t+2 through the shared head.
        mkeys = jax.random.split(keys[2], 3)
        params["mtp"] = {
            "norm_h": _norm_init(cfg, axes, dtype, (), abstract),
            "norm_e": _norm_init(cfg, axes, dtype, (), abstract),
            # combine h and emb(next) -> d as a normal+transposed tp pair
            # (the paper-layout-clean equivalent of DSv3's concat linear)
            "w_comb_h": PP.tp_linear_init(mkeys[0], cfg.d_model,
                                          cfg.d_model, axes, dtype=dtype,
                                          abstract=abstract),
            "w_comb_e": PP.tp_linear_init(
                jax.random.fold_in(mkeys[0], 1), cfg.d_model, cfg.d_model,
                axes, dtype=dtype, abstract=abstract),
            "w_comb_o": PP.tp_linear_init(
                jax.random.fold_in(mkeys[0], 2), cfg.d_model, cfg.d_model,
                axes, in_shard="y", out_shard="x", dtype=dtype,
                abstract=abstract),
            "block": {
                "norm1": _norm_init(cfg, axes, dtype, (), abstract),
                "mixer": _mixer_init(cfg.mixers()[-1], mkeys[1], cfg,
                                     axes, dtype, (), abstract),
                "norm2": _norm_init(cfg, axes, dtype, (), abstract),
                "ffn": _ffn_init("mlp", mkeys[2], cfg, axes, dtype, (),
                                 abstract),
            },
        }
    if cfg.arch_type == "vlm":
        vd = cfg.encoder.input_dim or cfg.d_model
        params["projector"] = {
            "w1": PP.tp_linear_init(keys[2], vd, cfg.d_model, axes,
                                    in_shard=None, out_shard="y",
                                    dtype=dtype, abstract=abstract),
            "w2": PP.tp_linear_init(keys[3], cfg.d_model, cfg.d_model,
                                    axes, in_shard="y", out_shard="x",
                                    dtype=dtype, abstract=abstract),
        }
    return params


# ---------------------------------------------------------------------- #
# forward
# ---------------------------------------------------------------------- #

def _block_apply(blk, kinds_i, h, cfg, axes, *, positions, mode, cache,
                 aux, paged=None):
    mixer, ffn = kinds_i
    # seq-sharded decode only changes the attention cache layout; the
    # recurrent mixers always do a plain single-step state update.
    # (mode 'paged' reaches softmax-attention mixers only —
    # decoder_paged_cache_specs gates the architecture up front.)
    sub_mode = "decode" if mode.startswith("decode") else mode
    hn = _apply_norm(blk["norm1"], h, cfg, axes)
    with trace.layer(mixer):
        if mixer == "attn":
            o, cache = A.attn_apply(blk["mixer"], hn, cfg, axes,
                                    positions=positions, mode=mode,
                                    cache=cache, window=cfg.sliding_window,
                                    paged=paged)
        elif mixer == "mla":
            o, cache = A.mla_apply(blk["mixer"], hn, cfg, axes,
                                   positions=positions, mode=sub_mode,
                                   cache=cache)
        elif mixer == "mamba":
            o, cache = MB.mamba_apply(blk["mixer"], hn, cfg, axes,
                                      mode=sub_mode, state=cache)
        elif mixer == "mlstm":
            o, cache = XL.mlstm_apply(blk["mixer"], hn, cfg, axes,
                                      mode=sub_mode, state=cache)
        elif mixer == "slstm":
            o, cache = XL.slstm_apply(blk["mixer"], hn, cfg, axes,
                                      mode=sub_mode, state=cache)
        else:
            raise ValueError(mixer)
        h = h + o
    if ffn != "none":
        hn = _apply_norm(blk["norm2"], h, cfg, axes)
        with trace.layer(ffn):
            if ffn == "moe":
                o, a = MOE.moe_apply(blk["ffn"], hn, cfg, axes)
                aux = aux + a
            else:
                o = FF.mlp_apply(blk["ffn"], hn, cfg.act, axes,
                                 gated=cfg.gated_mlp)
            h = h + o
    return h, cache, aux


def _checkpoint(fn, policy: str):
    if policy == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn)


def decoder_hidden(params, cfg: ArchConfig, axes: M.MeshAxes, tokens, *,
                   positions=None, mode: str = "train", caches=None,
                   image_embeds=None, remat: bool = True,
                   unroll: bool = False, remat_policy: str = "full",
                   pstream=None, paged=None):
    """Run embedding + all blocks. Returns (h, new_caches, aux_loss).

    ``pstream`` (a ``gradsync.ParamStreamer``, zero3 training only)
    switches the segment params to the ZeRO-3 shard layout: each scan
    iteration assembles just its layer's working copy by a ring
    all-gather over the data axis — inside the rematerialized body
    (released after the layer, re-gathered by remat for the backward)
    or, with ``pstream.prefetch``, one layer ahead via the carry (its
    ring hops overlap the current layer's compute; the copy is retained
    for the backward). Non-segment leaves must already be materialized
    (``pstream.resident`` — ``lm_loss`` does this)."""
    assert pstream is None or (mode == "train" and caches is None), \
        "zero3 param streaming is a training-path feature"
    if axes.gseq > 1 and mode != "train":
        raise NotImplementedError(
            f"seq (context) parallelism is a training-path feature: the "
            f"{mode!r} path keeps its KV cache whole per batch shard, so "
            f"a seq axis of g_seq={axes.gseq} has nothing to shard "
            f"(ROADMAP residual 'seq-parallel serving'). Serve on a mesh "
            f"with g_seq == 1 — e.g. pass a 4-tuple --mesh d,x,y,z, or "
            f"drop --seq-parallel/--g-seq from the launch flags.")
    B, T = tokens.shape
    if positions is None:
        if mode == "train" and axes.gseq > 1:
            # striped context-parallel layout (mesh.stripe_seq fed the
            # batch): local token j on seq-rank r is global position
            # j*g_seq + r — RoPE and causal masks both key off these
            positions = jnp.broadcast_to(
                jnp.arange(T, dtype=jnp.int32) * axes.gseq
                + M.axis_index(axes.seq), (B, T))
        else:
            positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32),
                                         (B, T))
    with trace.layer("vocab"):
        h = PP.embedding_lookup(tokens, params["embed"], axes)
    if cfg.arch_type == "vlm" and image_embeds is not None:
        assert axes.gseq == 1, \
            "image_embeds need a contiguous token prefix (no seq sharding)"
        assert image_embeds.shape[1] <= T, \
            f"image tokens {image_embeds.shape[1]} exceed seq {T}"
        pj = params["projector"]
        v = PP.tp_matmul(image_embeds, pj["w1"], axes, None, "y")
        v = PP.tp_matmul(jax.nn.gelu(v), pj["w2"], axes, "y", "x")
        h = jax.lax.dynamic_update_slice(
            h, v.astype(h.dtype), (0, 0, 0))

    aux0 = jnp.zeros((), jnp.float32)

    def make_period_fn(kinds):
        def period_fn(h, aux, blk_params, blk_caches):
            new_caches = {}
            for i in range(len(kinds)):
                c = None if blk_caches is None else blk_caches[f"pos{i}"]
                h, c, aux = _block_apply(
                    blk_params[f"pos{i}"], kinds[i], h, cfg, axes,
                    positions=positions, mode=mode, cache=c, aux=aux,
                    paged=paged)
                new_caches[f"pos{i}"] = c
            return h, aux, new_caches
        return period_fn

    aux = aux0
    new_caches = {} if caches is not None else None
    sbuckets = (pstream.buckets_like()["segments"]
                if pstream is not None else None)
    prefetch = pstream is not None and pstream.prefetch
    for s, (kinds, n_periods) in enumerate(cfg.segments()):
        seg_params = params["segments"][f"seg{s}"]
        seg_caches = None if caches is None else caches[f"seg{s}"]
        seg_bk = None if sbuckets is None else sbuckets[f"seg{s}"]
        # a segment streams only when its leaves are scan-stacked
        # (stack > 1). n_periods == 1 segments plan as unstacked —
        # ``pstream.resident`` already materialized their single layer
        # (= one layer's working set, the floor the schedule holds
        # anyway), so they run the plain non-streamed path below.
        streamed = (seg_bk is not None
                    and any(b.stack > 1 for b in jax.tree.leaves(seg_bk)))
        pre = streamed and prefetch
        period_fn = make_period_fn(kinds)
        if unroll:
            # python-unrolled layers: exact HLO flop/collective accounting
            # for the dry-run (XLA cost analysis counts a scan body once)
            ncs = [] if caches is not None else None

            def blk_fn(h, aux, blk, bc, _pf=period_fn, _bk=seg_bk,
                       _stream=streamed and not prefetch):
                # the just-in-time gather lives INSIDE the rematerialized
                # block: released after the layer's forward, re-gathered
                # by remat for its backward
                if _stream:
                    blk = pstream.gather_tree(blk, _bk)
                return _pf(h, aux, blk, bc)
            fn = blk_fn
            if remat and mode == "train":
                fn = _checkpoint(blk_fn, remat_policy)
            nxt = (pstream.gather_tree(
                jax.tree.map(lambda x: x[0], seg_params), seg_bk)
                if pre else None)
            for i in range(n_periods):
                if pre:
                    # issue layer i+1's gathers before layer i's compute:
                    # data-independent, so the scheduler overlaps them;
                    # the gathered copy is a block input -> retained for
                    # the backward (no re-gather)
                    blk, nxt = nxt, (pstream.gather_tree(
                        jax.tree.map(lambda x: x[i + 1], seg_params),
                        seg_bk) if i + 1 < n_periods else None)
                else:
                    blk = jax.tree.map(lambda x: x[i], seg_params)
                bc = (jax.tree.map(lambda x: x[i], seg_caches)
                      if caches is not None else None)
                h, aux, nc = fn(h, aux, blk, bc)
                if caches is not None:
                    ncs.append(nc)
            if caches is not None:
                new_caches[f"seg{s}"] = jax.tree.map(
                    lambda *xs: jnp.stack(xs), *ncs)
        elif caches is None:
            if pre:
                # gathered layer i+1 rides the carry while layer i
                # computes (retained as a saved carry for the backward);
                # the scan runs layers 0..n-2 over layer 1..n-1's shards
                # and the LAST layer applies outside it, so no gather is
                # ever issued for a layer that does not run
                first = pstream.gather_tree(
                    jax.tree.map(lambda x: x[0], seg_params), seg_bk)
                rest = jax.tree.map(lambda x: x[1:], seg_params)

                def body(carry, nxt_shards, _pf=period_fn, _bk=seg_bk):
                    h, aux, blk = carry
                    nxt = pstream.gather_tree(nxt_shards, _bk)
                    h, aux, _ = _pf(h, aux, blk, None)
                    return (h, aux, nxt), 0

                def last_fn(h, aux, blk, _pf=period_fn):
                    h, aux, _ = _pf(h, aux, blk, None)
                    return h, aux
                if remat and mode == "train":
                    body = _checkpoint(body, remat_policy)
                    last_fn = _checkpoint(last_fn, remat_policy)
                (h, aux, last), _ = jax.lax.scan(body, (h, aux, first),
                                                 rest)
                h, aux = last_fn(h, aux, last)
            else:
                def body(h_aux, blk_params, _pf=period_fn, _bk=seg_bk,
                         _stream=streamed):
                    if _stream:
                        blk_params = pstream.gather_tree(blk_params, _bk)
                    h, aux, _ = _pf(*h_aux, blk_params, None)
                    return (h, aux), 0
                if remat and mode == "train":
                    body = _checkpoint(body, remat_policy)
                (h, aux), _ = jax.lax.scan(body, (h, aux), seg_params)
        else:
            def body(h_aux, xs, _pf=period_fn):
                blk_params, blk_caches = xs
                h, aux, nc = _pf(*h_aux, blk_params, blk_caches)
                return (h, aux), nc
            (h, aux), nc = jax.lax.scan(body, (h, aux),
                                        (seg_params, seg_caches))
            new_caches[f"seg{s}"] = nc

    h = _apply_norm(params["final_norm"], h, cfg, axes)
    return h, new_caches, aux


def lm_logits(params, cfg: ArchConfig, axes: M.MeshAxes, h):
    """(B, T, d/x) -> (B, T, V/y) logits (replicated over x)."""
    if cfg.tie_embeddings:
        return PP.tied_lm_logits(h, params["embed"], axes)
    return PP.tp_matmul(h, params["lm_head"], axes, "x", "y")


def lm_loss(params, cfg: ArchConfig, axes: M.MeshAxes, tokens, labels, *,
            image_embeds=None, remat: bool = True,
            xent_chunks: int = 1, unroll: bool = False,
            remat_policy: str = "full", mtp_weight: float = 0.0,
            pstream=None):
    """Mean cross-entropy over the *global* batch (+ MoE aux loss,
    + optional DeepSeek-style MTP loss when configured and weighted).

    With ``pstream`` (zero3) ``params`` arrive as the ZeRO-3 shard tree:
    the non-streamed leaves (embedding, head, norms, mtp, projector) are
    materialized once here, the segment leaves stay sharded and stream
    per-layer through ``decoder_hidden``."""
    if pstream is not None:
        params = pstream.resident(params)
    h, _, aux = decoder_hidden(params, cfg, axes, tokens, mode="train",
                               image_embeds=image_embeds, remat=remat,
                               unroll=unroll, remat_policy=remat_policy,
                               pstream=pstream)
    B, T = labels.shape

    def chunk_loss(hc, lc):
        with trace.layer("vocab"):
            logits = lm_logits(params, cfg, axes, hc)
            return jnp.sum(PP.vocab_parallel_xent(logits, lc, axes,
                                                  cfg.vocab_size))

    if xent_chunks > 1 and T % xent_chunks == 0:
        hs = h.reshape(B, xent_chunks, T // xent_chunks, -1)
        ls = labels.reshape(B, xent_chunks, T // xent_chunks)
        total = 0.0
        for i in range(xent_chunks):
            total = total + chunk_loss(hs[:, i], ls[:, i])
    else:
        total = chunk_loss(h, labels)

    # token_axes() == batch_axes() + seq: under context parallelism each
    # seq-rank holds T/g_seq tokens, so the mean reduces over both.  With
    # seq unmapped these degenerate bitwise to the old batch reductions.
    total = PP.ar_bwd_identity(total, axes.token_axes())
    n_tokens_global = B * T * axes.token_shards
    loss = total / n_tokens_global
    aux_mean = PP.ar_bwd_identity(aux, axes.token_axes()) / axes.token_shards
    out_loss = loss + aux_mean
    metrics = {"xent": loss, "aux": aux_mean}
    if mtp_weight > 0.0 and "mtp" in params and T > 2:
        assert axes.gseq == 1, \
            "MTP needs contiguous token shifts (no seq sharding)"
        mtp = params["mtp"]
        # predict token t+2 from (h_t, emb(token_{t+1}))  [DSv3 MTP d=1]
        hn = _apply_norm(mtp["norm_h"], h[:, :-2, :], cfg, axes)
        emb = PP.embedding_lookup(tokens[:, 1:-1], params["embed"], axes)
        en = _apply_norm(mtp["norm_e"], emb, cfg, axes)
        u = PP.tp_matmul(hn, mtp["w_comb_h"], axes, "x", "y") \
            + PP.tp_matmul(en, mtp["w_comb_e"], axes, "x", "y")
        hm = PP.tp_matmul(jax.nn.gelu(u), mtp["w_comb_o"], axes, "y", "x")
        Bm, Tm = hm.shape[:2]
        pos = jnp.broadcast_to(jnp.arange(Tm, dtype=jnp.int32), (Bm, Tm))
        hm, _, _ = (lambda hh: _block_apply(
            mtp["block"], (cfg.mixers()[-1], "mlp"), hh, cfg, axes,
            positions=pos, mode="train", cache=None,
            aux=jnp.zeros((), jnp.float32)))(hm)
        logits_m = lm_logits(params, cfg, axes, hm)
        mtp_tok = PP.vocab_parallel_xent(logits_m, labels[:, 1:-1], axes,
                                         cfg.vocab_size)
        mtp_total = PP.ar_bwd_identity(jnp.sum(mtp_tok), axes.batch_axes())
        mtp_loss = mtp_total / (Bm * Tm * axes.batch_shards)
        out_loss = out_loss + mtp_weight * mtp_loss
        metrics["mtp"] = mtp_loss
    return out_loss, metrics


# ---------------------------------------------------------------------- #
# serving: cache specs + decode step
# ---------------------------------------------------------------------- #

def decoder_cache_specs(cfg: ArchConfig, axes: M.MeshAxes, batch_global: int,
                        seq: int, *, seqshard: bool = False,
                        dtype=jnp.bfloat16):
    """GLOBAL (ShapeDtypeStruct, PartitionSpec) trees for the decode cache,
    stacked (n_periods, ...) per segment position for the layer scans."""
    out = {}
    for s, (kinds, n_periods) in enumerate(cfg.segments()):
        seg = {}
        for i, (mixer, _) in enumerate(kinds):
            if mixer == "attn":
                spec = A.attn_cache_spec(cfg, axes, batch_global, seq,
                                         dtype=dtype, seqshard=seqshard)
            elif mixer == "mla":
                assert not seqshard, "MLA long-context seqshard unsupported"
                spec = A.mla_cache_spec(cfg, axes, batch_global, seq,
                                        dtype=dtype)
            elif mixer == "mamba":
                spec = MB.mamba_state_spec(cfg, axes, batch_global,
                                           dtype=dtype, seqshard=seqshard)
            elif mixer in ("mlstm", "slstm"):
                spec = XL.xlstm_state_spec(cfg, axes, batch_global, mixer,
                                           seqshard=seqshard)
            else:
                raise ValueError(mixer)
            seg[f"pos{i}"] = jax.tree.map(
                lambda sp: (jax.ShapeDtypeStruct(
                    (n_periods, *sp[0].shape), sp[0].dtype),
                    P(None, *sp[1])),
                spec, is_leaf=lambda t: isinstance(t, tuple)
                and len(t) == 2 and isinstance(t[0], jax.ShapeDtypeStruct))
        out[f"seg{s}"] = seg
    return out


def decode_step(params, cfg: ArchConfig, axes: M.MeshAxes, tokens, caches,
                pos, *, seqshard: bool = False, unroll: bool = False):
    """One serving step: tokens (B, 1) at absolute position ``pos``.

    Returns (logits (B, 1, V/y), new_caches)."""
    B = tokens.shape[0]
    positions = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B, 1))
    mode = "decode_seqshard" if seqshard else "decode"
    h, new_caches, _ = decoder_hidden(params, cfg, axes, tokens,
                                      positions=positions, mode=mode,
                                      caches=caches, remat=False,
                                      unroll=unroll)
    logits = lm_logits(params, cfg, axes, h)
    return logits, new_caches


def decoder_paged_cache_specs(cfg: ArchConfig, axes: M.MeshAxes,
                              n_pages_global: int, page_size: int, *,
                              dtype=jnp.bfloat16):
    """GLOBAL (struct, spec) trees for the PAGED serving cache: one
    physical KV page pool per attention layer (pages sharded over
    data x z, KV heads over y — ``A.paged_attn_cache_spec``), stacked
    (n_periods, ...) per segment position like ``decoder_cache_specs``.

    Paged serving gates to text decoders whose mixers are all softmax
    attention: recurrent mixers (mamba/xlstm) keep per-slot dense state
    with no page analogue, and MLA's absorbed decode reads its compressed
    cache contiguously."""
    bad = sorted({m for m in cfg.mixers() if m != "attn"})
    if bad or cfg.arch_type in ("vlm", "audio"):
        what = (f"mixer(s) {bad}" if bad
                else f"arch_type {cfg.arch_type!r}")
        raise NotImplementedError(
            f"{cfg.name}: paged continuous-batching serving supports "
            f"text decoders with softmax-attention mixers only (got "
            f"{what}). Use the fixed-batch path instead: "
            f"python -m repro.launch.serve --mode fixed --arch {cfg.name}")
    out = {}
    for s, (kinds, n_periods) in enumerate(cfg.segments()):
        seg = {}
        for i, _ in enumerate(kinds):
            spec = A.paged_attn_cache_spec(cfg, axes, n_pages_global,
                                           page_size, dtype=dtype)
            seg[f"pos{i}"] = jax.tree.map(
                lambda sp: (jax.ShapeDtypeStruct(
                    (n_periods, *sp[0].shape), sp[0].dtype),
                    P(None, *sp[1])),
                spec, is_leaf=lambda t: isinstance(t, tuple)
                and len(t) == 2 and isinstance(t[0], jax.ShapeDtypeStruct))
        out[f"seg{s}"] = seg
    return out


def paged_step(params, cfg: ArchConfig, axes: M.MeshAxes, tokens, pools,
               positions, q_len, table):
    """One continuous-batching serving step over the paged KV cache.

    tokens (R, T): slot r's rows 0..q_len[r]-1 carry its prefill chunk
    (or single decode token) at global ``positions`` (R, T); rows past
    q_len[r] are padding (idle slots have q_len 0). ``table`` (R,
    max_pages) holds shard-local physical page ids. Returns (per-slot
    next-token logits from the last *valid* row, (R, 1, V/y), new
    pools). See docs/serving.md for the schedule this slots into."""
    paged = {"table": table, "q_len": q_len}
    h, new_pools, _ = decoder_hidden(params, cfg, axes, tokens,
                                     positions=positions, mode="paged",
                                     caches=pools, remat=False, paged=paged)
    idx = jnp.clip(q_len.astype(jnp.int32) - 1, 0, tokens.shape[1] - 1)
    hl = jnp.take_along_axis(h, idx[:, None, None], axis=1)
    logits = lm_logits(params, cfg, axes, hl)
    return logits, new_pools


def prefill(params, cfg: ArchConfig, axes: M.MeshAxes, tokens, caches, *,
            image_embeds=None, unroll: bool = False):
    """Fill the cache from a prompt; returns (logits_last, caches)."""
    h, new_caches, _ = decoder_hidden(params, cfg, axes, tokens,
                                      mode="prefill", caches=caches,
                                      image_embeds=image_embeds,
                                      remat=False, unroll=unroll)
    logits = lm_logits(params, cfg, axes, h[:, -1:, :])
    return logits, new_caches
