"""4D tensor-parallel primitives (paper Algorithm 1 + §4.1 + the z axis).

All cross-device communication on the differentiated path goes through
``jax.custom_vjp`` so the backward pass issues *exactly* the paper's
collective schedule (Algorithm 1 lines 6/13 plus the 4D z-axis weight
collectives) — naive autodiff of ``lax.psum`` inside a manual ``shard_map``
would both double-count replicated cotangents and emit redundant
collectives.

Layout invariant (see DESIGN.md):
  * residual stream: features sharded over ``x``, replicated over ``y``,
    batch sharded over ``data x z``.
  * "normal" layer  (paper: non-transposed): W[k/x, n/(y*z)]; forward
    all-reduce over ``x``; output features sharded over ``y``.
  * "transposed" layer (paper §4.1): W[k/y, n/(x*z)]; forward all-reduce
    over ``y``; output features sharded over ``x`` — i.e. back to the
    residual layout with zero layer-boundary communication.

These functions are only valid inside a ``shard_map`` over the bound mesh.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import collective_matmul as CMM
from repro.core import mesh as M
from repro.core import trace
from repro.core.overlap import OverlapConfig  # noqa: F401  (re-export)
from repro.core.partition import Boxed
from repro.kernels import grouped_matmul as GM

# The overlap knobs (ring-decomposed z collectives, weight-gather caching)
# ride on ``axes.overlap`` — an OverlapConfig bound via
# ``axes.with_overlap(...)`` (see core/overlap.py and EXPERIMENTS.md
# §Perf). The old module-global CACHE_WEIGHT_GATHER trace-time flag is
# subsumed by ``axes.overlap.cache_weight_gather``.


# ---------------------------------------------------------------------- #
# small helpers
# ---------------------------------------------------------------------- #

def _mm(a, b, out_dtype=None):
    """(..., k) @ (k, n) with fp32 accumulation on the MXU."""
    out = jax.lax.dot_general(
        a, b, (((a.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return out.astype(out_dtype or a.dtype)


def _logical(axes: M.MeshAxes, shard: Optional[str]):
    """Map a logical shard tag ('x', 'y', None) to mesh axis names."""
    if shard is None:
        return None
    if shard == "x":
        return axes.x
    if shard == "y":
        return axes.y
    raise ValueError(f"bad shard tag {shard!r}")


def _axes_for(axes: M.MeshAxes, transposed: bool):
    """(contraction axis, output axis) — swapped for transposed layers."""
    return (axes.y, axes.x) if transposed else (axes.x, axes.y)


def _zring(axes: M.MeshAxes, enabled: bool):
    """Mesh axis name(s) for the fused z ring path, or None for blocking.

    Single- and multi-name (tuple) z axes both ring (the drivers flatten
    tuples into one combined ring); unmapped/size-1 z falls back to the
    blocking schedule (which is itself an identity over z there)."""
    if not enabled:
        return None
    n = M._names(axes.z)
    if not n or axes.gz <= 1:
        return None
    return n[0] if len(n) == 1 else n


def _arring(axes: M.MeshAxes, ax):
    """Ring axis name(s) for an activation all-reduce over ``ax`` under
    ``overlap.all_reduce``, or None for the blocking psum."""
    if not axes.overlap.all_reduce:
        return None
    n = M._names(ax)
    if not n or axes.size(ax) <= 1:
        return None
    return n[0] if len(n) == 1 else n


def _ar(v, axes: M.MeshAxes, ax):
    """All-reduce ``v`` over ``ax``: ring-decomposed over the last dim
    when ``overlap.all_reduce`` is on (with ring_all_reduce's own
    fallbacks for p == 1 / non-dividing shapes), else blocking psum."""
    if _arring(axes, ax) is not None:
        return M.ring_all_reduce(v, ax, dim=-1)
    return M.psum(v, ax)


def wspec(axes: M.MeshAxes, in_shard: Optional[str], out_shard: Optional[str]
          ) -> P:
    """PartitionSpec for a tp weight W[k, n]: k over the contraction shard,
    n over (output shard, z)."""
    in_ax = _logical(axes, in_shard)
    out_names = M._names(_logical(axes, out_shard)) + M._names(axes.z)
    return axes.pspec(in_ax, out_names if out_names else None)


def yz_spec(axes: M.MeshAxes, transposed: bool) -> P:
    return wspec(axes, *(('y', 'x') if transposed else ('x', 'y')))


# ---------------------------------------------------------------------- #
# replicated-cotangent all-reduce (Megatron's "g" operator)
# ---------------------------------------------------------------------- #

@partial(jax.custom_vjp, nondiff_argnums=(1,))
def ar_bwd_identity(v, axis):
    """Forward all-reduce; backward identity.

    Correct when the consumer treats the output as replicated over ``axis``
    (so the incoming cotangent is itself replicated)."""
    return M.psum(v, axis)


def _arbi_fwd(v, axis):
    return M.psum(v, axis), None


def _arbi_bwd(axis, _, dy):
    return (dy,)


ar_bwd_identity.defvjp(_arbi_fwd, _arbi_bwd)


# ---------------------------------------------------------------------- #
# the 4D tensor-parallel matmul (paper Algorithm 1 + z axis)
# ---------------------------------------------------------------------- #

@partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def tp_matmul(x, w, axes: M.MeshAxes, in_shard: Optional[str] = "x",
              out_shard: Optional[str] = "y"):
    """Y = X @ W with the paper's 4D collective schedule.

    x: (..., k_local)  — features sharded over ``in_shard`` (or replicated),
                         replicated over ``out_shard``.
    w: (k_local, n_local/z) — z-sharded storage, rows over ``in_shard``,
                              cols over ``out_shard``.
    returns (..., n_local) sharded over ``out_shard``, replicated over
    ``in_shard``.

    (in_shard='x', out_shard='y') is a paper "normal" layer, ('y', 'x') a
    paper "transposed" layer (§4.1); (x, None)/(None, y)/... cover shared
    projections (MLA latents, MoE routers, modality projectors).

    With ``axes.overlap.matmul`` set, the z-axis weight collectives run as
    ring-decomposed collective matmuls (core/collective_matmul.py): the
    forward AG_z becomes per-chunk GEMMs interleaved with ``ppermute``
    hops, the backward dW reduce-scatter a fused RS-matmul. With
    ``axes.overlap.all_reduce`` the x/y *activation* all-reduces (fwd
    line 6, bwd line 13) additionally decompose into reduce-scatter +
    all-gather rings — fused with the producing GEMM whenever the full
    weight is materialized. The collective *schedule* (what is reduced
    where) is unchanged — only its decomposition, so results match
    within fp32-accum reassociation.
    """
    in_ax = _logical(axes, in_shard)
    ov = axes.overlap
    ring = _zring(axes, ov.matmul)
    ar = _arring(axes, in_ax)
    if ring is None:
        wf = M.all_gather(w, axes.z, dim=1)        # AG_z (4D)
        if ar is not None:                          # fused GEMM + AR ring
            return CMM.ar_matmul(x, wf, ar, chunks=ov.ar_chunks)
        y = _mm(x, wf)                              # local GEMM (line 6)
    else:
        y = CMM.ag_matmul(x, w, ring, chunks=ov.z_chunks)
    return _ar(y, axes, in_ax)                      # All-Reduce_c (line 6)


def _tpmm_fwd(x, w, axes, in_shard, out_shard):
    in_ax = _logical(axes, in_shard)
    ov = axes.overlap
    ring = _zring(axes, ov.matmul)
    ar = _arring(axes, in_ax)
    # paper line 7 caches the *local* partitions; by default we re-gather
    # over z in the backward pass to keep the z-sharded weight footprint
    # (overlap.cache_weight_gather keeps wf and saves one AG_z).
    if ov.cache_weight_gather:
        wf = (M.ring_all_gather(w, axes.z, dim=1) if ring is not None
              else M.all_gather(w, axes.z, dim=1))
        y = (CMM.ar_matmul(x, wf, ar, chunks=ov.ar_chunks)
             if ar is not None else M.psum(_mm(x, wf), in_ax))
        return y, (x, w, wf)
    if ring is None:
        wf = M.all_gather(w, axes.z, dim=1)
        if ar is not None:
            return CMM.ar_matmul(x, wf, ar, chunks=ov.ar_chunks), (x, w, None)
        y = _mm(x, wf)
    else:
        y = CMM.ag_matmul(x, w, ring, chunks=ov.z_chunks)
    return _ar(y, axes, in_ax), (x, w, None)


def _tpmm_bwd(axes, in_shard, out_shard, res, dy):
    x, w, wf = res
    ov = axes.overlap
    ring = _zring(axes, ov.matmul)
    out_ax = _logical(axes, out_shard)
    ar = _arring(axes, out_ax)
    # dX = All-Reduce_r(dY @ W^T)  (line 13); the z re-gather of W fuses
    # into the GEMM as a ring over the contraction segments
    if wf is None and ring is not None:
        dx = CMM.accum_matmul_dx(dy, w, ring,
                                 chunks=ov.z_chunks).astype(x.dtype)
        dx = _ar(dx, axes, out_ax)
    elif ar is not None:
        if wf is None:
            wf = M.all_gather(w, axes.z, dim=1)    # re-gather (AG_z)
        dx = CMM.ar_matmul_t(dy, wf, ar, chunks=ov.ar_chunks)
    else:
        if wf is None:
            wf = M.all_gather(w, axes.z, dim=1)    # re-gather (AG_z)
        dx = jax.lax.dot_general(
            dy, wf, (((dy.ndim - 1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32).astype(x.dtype)
        dx = M.psum(dx, out_ax)
    # dW = X^T @ dY, reduce-scattered over z (line 14 + 4D)
    k = x.shape[-1]
    n = dy.shape[-1]
    if ring is not None:
        dw = CMM.rs_matmul_dw(x.reshape(-1, k), dy.reshape(-1, n), ring,
                              block_w=w.shape[1], chunks=ov.z_chunks)
    else:
        dw = jax.lax.dot_general(
            x.reshape(-1, k), dy.reshape(-1, n),
            (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dw = M.psum_scatter(dw, axes.z, dim=1)
    return dx, dw.astype(w.dtype)


tp_matmul.defvjp(_tpmm_fwd, _tpmm_bwd)


def tp_matmul_t(x, w, axes: M.MeshAxes):
    """Paper 'transposed' layer: contract over y, output over x."""
    return tp_matmul(x, w, axes, "y", "x")


# ---------------------------------------------------------------------- #
# batched (per-expert) tp matmul: x (E, ..., k) @ w (E, k, n/z)
# ---------------------------------------------------------------------- #

def _bmm(a, b):
    """(E, m, k) @ (E, k, n) with fp32 accumulation."""
    return jax.lax.dot_general(
        a, b, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32).astype(a.dtype)


@partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def tp_batched_matmul(x, w, axes: M.MeshAxes, in_shard: Optional[str],
                      out_shard: Optional[str]):
    """Per-expert matmul with the same 4D collective schedule as tp_matmul.

    x: (E_local, C, k_local); w: (E_local, k_local, n_local/z).
    The expert dim E is itself sharded over ``y`` by the caller (MoE), so
    ``in_shard``/``out_shard`` here are 'x' or None.

    ``axes.overlap.batched_matmul`` rings the z collectives exactly as in
    tp_matmul; ``axes.overlap.all_reduce`` rings the activation
    all-reduces."""
    ov = axes.overlap
    in_ax = _logical(axes, in_shard)
    ring = _zring(axes, ov.batched_matmul)
    ar = _arring(axes, in_ax)
    if ring is None:
        wf = M.all_gather(w, axes.z, dim=2)
        if ar is not None:
            return CMM.ar_matmul_batched(x, wf, ar, chunks=ov.ar_chunks)
        y = _bmm(x, wf)
    else:
        y = CMM.ag_matmul_batched(x, w, ring, chunks=ov.z_chunks)
    return _ar(y, axes, in_ax)


def _tpbmm_fwd(x, w, axes, in_shard, out_shard):
    y = tp_batched_matmul.__wrapped__(x, w, axes, in_shard, out_shard)
    return y, (x, w)


def _tpbmm_bwd(axes, in_shard, out_shard, res, dy):
    x, w = res
    ov = axes.overlap
    ring = _zring(axes, ov.batched_matmul)
    out_ax = _logical(axes, out_shard)
    ar = _arring(axes, out_ax)
    if ring is None:
        wf = M.all_gather(w, axes.z, dim=2)
        if ar is not None:
            dx = CMM.ar_matmul_batched_t(dy, wf, ar, chunks=ov.ar_chunks)
        else:
            dx = jax.lax.dot_general(
                dy, wf, (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)
            dx = M.psum(dx.astype(x.dtype), out_ax)
    else:
        dx = CMM.accum_matmul_dx_batched(dy, w, ring, chunks=ov.z_chunks)
        dx = _ar(dx.astype(x.dtype), axes, out_ax)
    if ring is None:
        dw = jax.lax.dot_general(
            x, dy, (((1,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        dw = M.psum_scatter(dw, axes.z, dim=2)
    else:
        dw = CMM.rs_matmul_dw_batched(x, dy, ring, block_w=w.shape[2],
                                      chunks=ov.z_chunks)
    return dx, dw.astype(w.dtype)


tp_batched_matmul.defvjp(_tpbmm_fwd, _tpbmm_bwd)


# ---------------------------------------------------------------------- #
# grouped (per-expert, ragged) tp matmul: rows sorted by expert
# ---------------------------------------------------------------------- #

@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def tp_grouped_matmul(x, w, sizes, axes: M.MeshAxes,
                      in_shard: Optional[str], out_shard: Optional[str]):
    """Grouped matmul (``kernels.grouped_matmul``) with the blocking 4D
    schedule of :func:`tp_batched_matmul`: x (m, k_local) rows sorted by
    expert, w (E_local, k_local, n_local/z), sizes (E_local,) int32.
    The weights are gathered over z; the product is all-reduced over
    ``in_shard``; the backward all-reduces dX over ``out_shard`` and
    reduce-scatters dW over z. Rows past ``sum(sizes)`` are unspecified,
    forward and backward. The overlap settings that would ring these
    collectives (``overlap.batched_matmul`` over z, ``overlap.all_reduce``
    over x) are refused, not ignored: this path has no ring schedule."""
    rings = {"batched_matmul": _zring(axes, axes.overlap.batched_matmul),
             "all_reduce": _arring(axes, _logical(axes, in_shard))
             or _arring(axes, _logical(axes, out_shard))}
    on = sorted(k for k, r in rings.items() if r is not None)
    if on:
        raise NotImplementedError(
            f"overlap.{'/'.join(on)}: the dropless MoE dispatch "
            "(g_expert 1) runs its grouped products on the blocking "
            "schedule only")
    wf = M.all_gather(w, axes.z, dim=2)
    return M.psum(GM.grouped_matmul(x, wf, sizes),
                  _logical(axes, in_shard))


def _tpgmm_fwd(x, w, sizes, axes, in_shard, out_shard):
    y = tp_grouped_matmul.__wrapped__(x, w, sizes, axes, in_shard,
                                      out_shard)
    return y, (x, w, sizes)


def _tpgmm_bwd(axes, in_shard, out_shard, res, dy):
    x, w, sizes = res
    wf = M.all_gather(w, axes.z, dim=2)
    _, vjp = jax.vjp(lambda a, b: GM.grouped_matmul(a, b, sizes), x, wf)
    dx, dw = vjp(dy.astype(x.dtype))
    dx = M.psum(dx, _logical(axes, out_shard))
    dw = M.psum_scatter(dw.astype(jnp.float32), axes.z, dim=2)
    return dx, dw.astype(w.dtype), None


tp_grouped_matmul.defvjp(_tpgmm_fwd, _tpgmm_bwd)


def tp_expert_init(key, n_experts: int, k: int, n: int,
                   axes: M.MeshAxes, *, in_shard: Optional[str] = "x",
                   out_shard: Optional[str] = None, dtype=jnp.float32,
                   stack: Tuple[int, ...] = (),
                   abstract: bool = False) -> Boxed:
    """Expert weight bank (E, k, n): E over (y, expert) — y-major,
    expert-inner, so the layout reduces to today's y-only placement at
    g_expert = 1 — k over in_shard, n over (out_shard, z)."""
    in_ax = _logical(axes, in_shard)
    out_names = M._names(_logical(axes, out_shard)) + M._names(axes.z)
    e_names = M._names(axes.y) + M._names(axes.expert)
    spec = P(*([None] * len(stack)),
             *axes.pspec(e_names if e_names else None, in_ax,
                         out_names if out_names else None))
    shape = (*stack, n_experts, k, n)
    if abstract:
        return Boxed(jax.ShapeDtypeStruct(shape, dtype), spec, z_reduced=True)
    v = (jax.random.normal(key, shape, jnp.float32) / math.sqrt(k)
         ).astype(dtype)
    return Boxed(v, spec, z_reduced=True)


def tp_linear_init(key, k: int, n: int, axes: M.MeshAxes, *,
                   in_shard: Optional[str] = "x",
                   out_shard: Optional[str] = "y", dtype=jnp.float32,
                   stack: Tuple[int, ...] = (), scale: Optional[float] = None,
                   abstract: bool = False) -> Boxed:
    """Initialize a (stack of) tp weight(s) with its PartitionSpec.

    Raises if n cannot shard over (out_shard x z) — the factor chooser
    (launch/dryrun.choose_factors) probes feasibility via abstract init
    and skips infeasible decompositions."""
    shape = (*stack, k, n)
    out_ax = _logical(axes, out_shard)
    denom = axes.size(out_ax) * axes.size(axes.z)
    if denom and n % denom:
        raise ValueError(f"weight n={n} not divisible by out*z={denom}")
    in_ax = _logical(axes, in_shard)
    if axes.size(in_ax) and k % max(axes.size(in_ax), 1):
        raise ValueError(f"weight k={k} not divisible by in={in_ax}")
    spec = wspec(axes, in_shard, out_shard)
    spec = P(*([None] * len(stack)), *spec)
    if abstract:
        return Boxed(jax.ShapeDtypeStruct(shape, dtype), spec,
                     z_reduced=True)
    s = scale if scale is not None else 1.0 / math.sqrt(k)
    v = (jax.random.normal(key, shape, jnp.float32) * s).astype(dtype)
    return Boxed(v, spec, z_reduced=True)


def tp_bias_init(n: int, axes: M.MeshAxes, *, out_shard: Optional[str] = "y",
                 dtype=jnp.float32, stack: Tuple[int, ...] = (),
                 abstract: bool = False) -> Boxed:
    spec = P(*([None] * len(stack)), *axes.pspec(_logical(axes, out_shard)))
    shape = (*stack, n)
    if abstract:
        return Boxed(jax.ShapeDtypeStruct(shape, dtype), spec)
    return Boxed(jnp.zeros(shape, dtype), spec)


# ---------------------------------------------------------------------- #
# vocab-parallel embedding (rows over y, cols over (x, z))
# ---------------------------------------------------------------------- #

@partial(jax.custom_vjp, nondiff_argnums=(2,))
def embedding_lookup(tokens, table, axes: M.MeshAxes):
    """tokens (B, S) int32; table (V_local, H_local/z).

    Output: (B, S, H_local) — features sharded over x, replicated over y."""
    out, _ = _emb_fwd(tokens, table, axes)
    return out


def _emb_fwd(tokens, table, axes):
    with trace.scope("embed_gather", axes.z):
        if axes.overlap.embed_gather:
            # ring-decomposed AG_z: same blocks in the same positions
            # (bitwise-identical result), but as a ppermute chain the
            # scheduler can start the lookup on resident shards early
            tf = M.ring_all_gather(table, axes.z, dim=1)
        else:
            tf = M.all_gather(table, axes.z, dim=1)
    v_local = tf.shape[0]
    start = M.axis_index(axes.y) * v_local
    local = tokens - start
    ok = (local >= 0) & (local < v_local)
    emb = jnp.take(tf, jnp.clip(local, 0, v_local - 1), axis=0)
    emb = jnp.where(ok[..., None], emb, 0).astype(tf.dtype)
    emb = ar_bwd_identity(emb, axes.y)   # assemble across vocab shards
    return emb, (tokens, table)


def _emb_bwd(axes, res, demb):
    tokens, table = res
    tf_shape0 = table.shape[0]
    start = M.axis_index(axes.y) * tf_shape0
    local = tokens - start
    ok = (local >= 0) & (local < tf_shape0)
    idx = jnp.where(ok, local, tf_shape0)  # out-of-range rows dropped
    h_full = demb.shape[-1]
    dtab = jnp.zeros((tf_shape0 + 1, h_full), jnp.float32)
    dtab = dtab.at[idx.reshape(-1)].add(
        demb.reshape(-1, h_full).astype(jnp.float32))[:-1]
    dtab = M.psum_scatter(dtab, axes.z, dim=1).astype(table.dtype)
    return None, dtab


embedding_lookup.defvjp(lambda t, tab, axes: _emb_fwd(t, tab, axes),
                        _emb_bwd)


def embedding_init(key, vocab: int, hidden: int, axes: M.MeshAxes, *,
                   dtype=jnp.float32, abstract: bool = False) -> Boxed:
    spec = axes.pspec(axes.y, M._names(axes.x) + M._names(axes.z))
    if abstract:
        return Boxed(jax.ShapeDtypeStruct((vocab, hidden), dtype), spec,
                     z_reduced=True)
    v = (jax.random.normal(key, (vocab, hidden), jnp.float32) * 0.02
         ).astype(dtype)
    return Boxed(v, spec, z_reduced=True)


# ---------------------------------------------------------------------- #
# layout rotation: full (x-replicated) features -> local x shard
# ---------------------------------------------------------------------- #

@partial(jax.custom_vjp, nondiff_argnums=(1,))
def to_x_shard(v, axes: M.MeshAxes):
    """Slice this rank's x-shard from a feature dim that is replicated
    over x (e.g. the output of a tp_matmul with out_shard=None). Backward
    all-gathers the sharded cotangents back to the replicated layout."""
    d_local = v.shape[-1] // max(axes.gx, 1)
    start = M.axis_index(axes.x) * d_local
    return jax.lax.dynamic_slice_in_dim(v, start, d_local, axis=-1)


def _toxs_fwd(v, axes):
    return to_x_shard.__wrapped__(v, axes), None


def _toxs_bwd(axes, _, dy):
    return (M.all_gather(dy, axes.x, dim=dy.ndim - 1),)


to_x_shard.defvjp(_toxs_fwd, _toxs_bwd)


# ---------------------------------------------------------------------- #
# tied-embedding LM head: logits = h @ table^T with the paper schedule
# ---------------------------------------------------------------------- #

@partial(jax.custom_vjp, nondiff_argnums=(2,))
def tied_lm_logits(h, table, axes: M.MeshAxes):
    """h (..., d/x) x-sharded; table (V/y, d/(x z)) — the embedding layout.

    Returns logits (..., V/y) replicated over x (same layout as an untied
    lm_head tp_matmul('x','y'))."""
    out, _ = _tied_fwd(h, table, axes)
    return out


def _tied_fwd(h, table, axes):
    ov = axes.overlap
    ring = _zring(axes, ov.tied_logits)
    ar = _arring(axes, axes.x)
    if ring is None:
        tf = M.all_gather(table, axes.z, dim=1)      # (V/y, d/x)
        if ar is not None:
            # reduced (V) dim indexes the table's rows: fused AR-matmul
            return CMM.ar_matmul_t(h, tf, ar, chunks=ov.ar_chunks), (h, table)
        logits = jax.lax.dot_general(
            h, tf, (((h.ndim - 1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
    else:
        # the gathered (d) dim is the contraction dim here: ring-
        # accumulate over the z segments of h against the table blocks
        logits = CMM.accum_matmul_tied(h, table, ring,
                                       chunks=ov.z_chunks)
    logits = _ar(logits.astype(h.dtype), axes, axes.x)
    return logits, (h, table)


def _tied_bwd(axes, res, dlogits):
    h, table = res
    ov = axes.overlap
    ring = _zring(axes, ov.tied_logits)
    ar = _arring(axes, axes.y)
    if ring is None:
        tf = M.all_gather(table, axes.z, dim=1)
        if ar is not None:
            dh = CMM.ar_matmul(dlogits, tf, ar, chunks=ov.ar_chunks)
        else:
            dh = jax.lax.dot_general(
                dlogits, tf, (((dlogits.ndim - 1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dh = M.psum(dh.astype(h.dtype), axes.y)
    else:
        dh = CMM.ag_matmul_tied_dh(dlogits, table, ring,
                                   chunks=ov.z_chunks)
        dh = _ar(dh.astype(h.dtype), axes, axes.y)
    v = dlogits.shape[-1]
    d = h.shape[-1]
    if ring is None:
        dt = jax.lax.dot_general(
            dlogits.reshape(-1, v), h.reshape(-1, d),
            (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        dt = M.psum_scatter(dt, axes.z, dim=1)
    else:
        dt = CMM.rs_matmul_tied_dt(dlogits.reshape(-1, v),
                                   h.reshape(-1, d), ring,
                                   block_w=table.shape[1],
                                   chunks=ov.z_chunks)
    return dh, dt.astype(table.dtype)


tied_lm_logits.defvjp(lambda h, t, axes: _tied_fwd(h, t, axes), _tied_bwd)


# ---------------------------------------------------------------------- #
# vocab-parallel softmax cross-entropy (fused, hand-written backward)
# ---------------------------------------------------------------------- #

@partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def vocab_parallel_xent(logits, labels, axes: M.MeshAxes,
                        valid_vocab: int = 0):
    """logits (..., V_local) sharded over y (replicated over x);
    labels (...) global ids. ``valid_vocab``: true vocab size (padded
    columns beyond it are masked out). Returns per-token loss (...)."""
    loss, _ = _xent_fwd(logits, labels, axes, valid_vocab)
    return loss


def _valid_mask(v_local, start, valid_vocab):
    if not valid_vocab:
        return None
    cols = start + jnp.arange(v_local)
    return cols < valid_vocab


def _xent_stats(logits, labels, axes, valid_vocab):
    lg = logits.astype(jnp.float32)
    v_local_ = lg.shape[-1]
    start_ = M.axis_index(axes.y) * v_local_
    vm = _valid_mask(v_local_, start_, valid_vocab)
    if vm is not None:
        lg = jnp.where(vm, lg, -1e30)
    m = M.pmax(jnp.max(lg, axis=-1), axes.y)
    se = M.psum(jnp.sum(jnp.exp(lg - m[..., None]), axis=-1), axes.y)
    lse = jnp.log(se) + m
    v_local = lg.shape[-1]
    start = M.axis_index(axes.y) * v_local
    local = labels - start
    ok = (local >= 0) & (local < v_local)
    tgt = jnp.take_along_axis(
        lg, jnp.clip(local, 0, v_local - 1)[..., None], axis=-1)[..., 0]
    tgt = M.psum(jnp.where(ok, tgt, 0.0), axes.y)
    return lse, tgt, local, ok, m


def _xent_fwd(logits, labels, axes, valid_vocab):
    lse, tgt, local, ok, _ = _xent_stats(logits, labels, axes, valid_vocab)
    return (lse - tgt), (logits, labels, lse)


def _xent_bwd(axes, valid_vocab, res, dloss):
    logits, labels, lse = res
    lg = logits.astype(jnp.float32)
    v_local_ = lg.shape[-1]
    start_ = M.axis_index(axes.y) * v_local_
    vm = _valid_mask(v_local_, start_, valid_vocab)
    if vm is not None:
        lg = jnp.where(vm, lg, -1e30)
    probs = jnp.exp(lg - lse[..., None])
    v_local = lg.shape[-1]
    start = M.axis_index(axes.y) * v_local
    local = labels - start
    ok = (local >= 0) & (local < v_local)
    onehot = jax.nn.one_hot(jnp.where(ok, local, -1), v_local,
                            dtype=jnp.float32)
    dlogits = (probs - onehot) * dloss[..., None].astype(jnp.float32)
    return dlogits.astype(logits.dtype), None


vocab_parallel_xent.defvjp(
    lambda l, t, axes, vv: _xent_fwd(l, t, axes, vv), _xent_bwd)


# ---------------------------------------------------------------------- #
# feature-sharded RMSNorm / LayerNorm (stats psum'd over x)
# ---------------------------------------------------------------------- #

@partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def rms_norm(x, gamma, axes: M.MeshAxes, full_dim: int, eps: float = 1e-6):
    """RMSNorm over a feature dim sharded across ``x``."""
    y, _ = _rms_fwd(x, gamma, axes, full_dim, eps)
    return y


def _rms_fwd(x, gamma, axes, full_dim, eps):
    xf = x.astype(jnp.float32)
    ms = M.psum(jnp.sum(xf * xf, axis=-1), axes.x) / full_dim
    r = jax.lax.rsqrt(ms + eps)
    y = (xf * r[..., None] * gamma.astype(jnp.float32)).astype(x.dtype)
    return y, (x, gamma, r)


def _rms_bwd(axes, full_dim, eps, res, dy):
    x, gamma, r = res
    xf = x.astype(jnp.float32)
    dyf = dy.astype(jnp.float32) * gamma.astype(jnp.float32)
    xhat = xf * r[..., None]
    # mean over the FULL feature dim -> psum over x
    dot = M.psum(jnp.sum(dyf * xhat, axis=-1), axes.x) / full_dim
    dx = (r[..., None] * (dyf - xhat * dot[..., None])).astype(x.dtype)
    dg = jnp.sum((dy.astype(jnp.float32) * xhat).reshape(-1, x.shape[-1]),
                 axis=0).astype(gamma.dtype)
    return dx, dg


rms_norm.defvjp(lambda x, g, axes, fd, eps: _rms_fwd(x, g, axes, fd, eps),
                _rms_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def layer_norm(x, gamma, beta, axes: M.MeshAxes, full_dim: int,
               eps: float = 1e-5):
    y, _ = _ln_fwd(x, gamma, beta, axes, full_dim, eps)
    return y


def _ln_fwd(x, gamma, beta, axes, full_dim, eps):
    xf = x.astype(jnp.float32)
    mu = M.psum(jnp.sum(xf, axis=-1), axes.x) / full_dim
    xc = xf - mu[..., None]
    var = M.psum(jnp.sum(xc * xc, axis=-1), axes.x) / full_dim
    r = jax.lax.rsqrt(var + eps)
    xhat = xc * r[..., None]
    y = (xhat * gamma.astype(jnp.float32) + beta.astype(jnp.float32)
         ).astype(x.dtype)
    return y, (xhat, gamma, r)


def _ln_bwd(axes, full_dim, eps, res, dy):
    xhat, gamma, r = res
    dyf = dy.astype(jnp.float32) * gamma.astype(jnp.float32)
    mean_dy = M.psum(jnp.sum(dyf, axis=-1), axes.x) / full_dim
    mean_dyx = M.psum(jnp.sum(dyf * xhat, axis=-1), axes.x) / full_dim
    dx = (r[..., None] * (dyf - mean_dy[..., None]
                          - xhat * mean_dyx[..., None])).astype(dy.dtype)
    dg = jnp.sum((dy.astype(jnp.float32) * xhat).reshape(-1, dy.shape[-1]),
                 axis=0).astype(gamma.dtype)
    db = jnp.sum(dy.astype(jnp.float32).reshape(-1, dy.shape[-1]),
                 axis=0).astype(gamma.dtype)
    return dx, dg, db


layer_norm.defvjp(lambda x, g, b, axes, fd, eps: _ln_fwd(x, g, b, axes, fd, eps),
                  _ln_bwd)


def norm_param_init(hidden: int, axes: M.MeshAxes, *, dtype=jnp.float32,
                    value: float = 1.0, stack: Tuple[int, ...] = (),
                    abstract: bool = False) -> Boxed:
    """A per-feature parameter sharded over x (residual layout)."""
    spec = P(*([None] * len(stack)), *axes.pspec(axes.x))
    shape = (*stack, hidden)
    if abstract:
        return Boxed(jax.ShapeDtypeStruct(shape, dtype), spec)
    return Boxed(jnp.full(shape, value, dtype), spec)
