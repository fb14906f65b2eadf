"""Ring-decomposed collective matmuls (overlapped z-axis schedule).

The blocking 4D schedule in :mod:`repro.core.parallel` materializes the
z-gathered weight (``AG_z`` then one big GEMM) and reduce-scatters the
full weight gradient (one big GEMM then ``RS_z``). Both serialize an
expensive collective against an expensive GEMM. Following the decomposed
collective-matmul technique (AxoNN, arXiv:2110.13005; survey
arXiv:2403.07585), the three drivers here re-express those collectives as
``lax.ppermute`` ring steps whose per-chunk GEMMs interleave with the
permutes, so each hop's communication hides under the previous chunk's
compute (XLA's latency-hiding scheduler sees p data-independent
(permute, GEMM) pairs instead of one barrier).

Ring convention (matches core/mesh ring helpers and the TPU RDMA idiom):
send right (rank i -> i+1), so after ``s`` hops rank ``i`` holds the block
originally owned by rank ``(i - s) mod p``.

Four dataflow patterns cover every x/y/z collective on the hot path:

  * place      — gathered dim is the GEMM's *output* dim:
                 ``out[..., slot_j] = mm(block_j)``            (AG-matmul)
  * accumulate — gathered dim is the GEMM's *contraction* dim:
                 ``out = sum_j mm(lhs[..., seg_j], block_j)``  (AG-matmul)
  * reduce-scatter — scatter dim is the GEMM's output dim:
                 partial sums ride the ring, each rank's GEMM contribution
                 is added just-in-time                         (RS-matmul)
  * all-reduce — the x/y *activation* all-reduce of a tp matmul as a
                 reduce-scatter ring fed per-chunk by the producing GEMM,
                 then an all-gather ring                       (AR-matmul)

``chunks > 1`` splits each per-rank block into independent sub-rings for
finer-grained permute/GEMM pairs (OverlapConfig.z_chunks / ar_chunks).

All drivers accumulate in fp32 (``preferred_element_type``), so results
match the blocking schedule within fp32-accumulation reassociation only.
Tuple (multi-name) mesh axes ring once over the flattened group — the
same FIRST-name-major linearization as a PartitionSpec tuple and
core/mesh's blocking helpers, so layouts stay interchangeable; ``p == 1``
degrades to the plain local GEMM with zero collectives.

Knob units and degeneracy guarantees (DESIGN.md §Overlapped schedule;
pinned by tests/test_overlap.py):

  * ``chunks`` — **sub-rings per per-rank block** (dimensionless;
    ``effective_chunks`` rounds down to the largest divisor of the block
    width, so any value is safe). ``chunks=1`` is one ring whose hops
    already interleave one GEMM each.
  * Every ring driver moves exactly the wire bytes of its blocking
    collective — the rings change *exposure*, never volume
    (``comm_model.layer_volume`` is ring-agnostic for this reason).
  * The forward place-ring is bitwise identical to AG-then-GEMM; the
    accumulate/reduce-scatter/all-reduce rings are bitwise on
    exactly-summable values and within fp32 reassociation otherwise.
  * In the α-β model a ring costs ``(p-1)·α`` (AG/RS) or ``2(p-1)·α``
    (AR) plus bandwidth-optimal bytes; measured α/β replacements come
    from core/calibrate.py.
"""
from __future__ import annotations

from typing import Callable, List, Tuple, Union

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import trace
from repro.core.mesh import flat_ring_axis, flat_ring_index, \
    ring_all_gather, ring_perm as _ring_perm

AxisRef = Union[str, Tuple[str, ...]]


def effective_chunks(width: int, chunks: int) -> int:
    """Largest c <= chunks dividing width (so odd shards never error)."""
    c = max(1, min(chunks, width))
    while width % c:
        c -= 1
    return c


# ---------------------------------------------------------------------- #
# generic drivers
# ---------------------------------------------------------------------- #

def ring_place(block, name: AxisRef, mm: Callable, *, gdim: int,
               chunks: int = 1):
    """``concat_j mm(block_of_rank_j)`` along the output's last dim.

    ``mm(piece) -> (..., piece_out)`` must map a block piece (sliced along
    ``gdim``) to its output chunk; rank j's block lands at slot j, pieces
    in slice order within the slot (identical to the blocking
    gather-then-GEMM layout).
    """
    p, axn = flat_ring_axis(name)
    if p == 1:
        return mm(block)
    idx = flat_ring_index(name)
    perm = _ring_perm(p)
    gdim = gdim % block.ndim
    chunks = effective_chunks(block.shape[gdim], chunks)
    m = block.shape[gdim] // chunks
    curs = [lax.slice_in_dim(block, q * m, (q + 1) * m, axis=gdim)
            for q in range(chunks)]
    ys: List[List] = []                         # ys[s][q]: hop s, piece q
    for s in range(p):
        with trace.scope("ring_ag", name, f"hop{s}"):
            nxt: List = []
            ys.append([])
            for q, cur in enumerate(curs):
                with trace.scope("gemm", None, f"chunk{q}"):
                    ys[s].append(mm(cur))
                if s < p - 1:
                    nxt.append(lax.ppermute(cur, axn, perm))
            curs = nxt
    # slot j holds rank j's block, which reached this rank at hop
    # (idx - j) mod p: a select on that scalar, which fuses with the
    # consumer, where a zero-filled output updated in place would not
    with trace.scope("ring_ag", name, "place"):
        return jnp.concatenate(
            [lax.select_n((idx - j) % p, *(y[q] for y in ys))
             for j in range(p) for q in range(chunks)], axis=-1)


def ring_accumulate(lhs, block, name: AxisRef, mm: Callable, *, gdim: int,
                    ldim: int = -1, chunks: int = 1):
    """``sum_j mm(lhs_seg_j, block_of_rank_j)`` — gathered contraction.

    ``lhs``'s ``ldim`` is segmented to match the gathered layout of the
    blocks: rank j's piece q contracts with ``lhs[..., (j*chunks+q)*m :]``.
    ``mm`` must return fp32 (partials are summed across the ring).
    """
    p, axn = flat_ring_axis(name)
    if p == 1:
        return mm(lhs, block)
    idx = flat_ring_index(name)
    perm = _ring_perm(p)
    gdim = gdim % block.ndim
    ldim = ldim % lhs.ndim
    chunks = effective_chunks(block.shape[gdim], chunks)
    m = block.shape[gdim] // chunks
    m_l = lhs.shape[ldim] // (p * chunks)
    curs = [lax.slice_in_dim(block, q * m, (q + 1) * m, axis=gdim)
            for q in range(chunks)]
    acc = None
    for s in range(p):
        with trace.scope("ring_ag", name, f"hop{s}"):
            j = (idx - s) % p
            nxt: List = []
            for q, cur in enumerate(curs):
                seg = lax.dynamic_slice_in_dim(
                    lhs, (j * chunks + q) * m_l, m_l, axis=ldim)
                with trace.scope("gemm", None, f"chunk{q}"):
                    y = mm(seg, cur)
                acc = y if acc is None else acc + y
                if s < p - 1:
                    nxt.append(lax.ppermute(cur, axn, perm))
            curs = nxt
    return acc


def ring_reduce_scatter_mm(name: AxisRef, mm: Callable, *, block_w: int,
                           chunks: int = 1):
    """Fused ``psum_scatter(full_contribution, name, dim=-1)`` where the
    full contribution never materializes.

    ``mm(start, width) -> fp32 (..., width)`` computes this rank's GEMM
    contribution to slice ``[start, start+width)`` of the scatter dim;
    ``block_w`` is the per-rank output block width. The partial destined
    for rank j is computed just-in-time as the running sum passes through
    (p GEMMs, p-1 permutes per sub-ring).
    """
    p, axn = flat_ring_axis(name)
    if p == 1:
        return mm(jnp.int32(0), block_w)
    idx = flat_ring_index(name)
    perm = _ring_perm(p)
    chunks = effective_chunks(block_w, chunks)
    m = block_w // chunks
    outs = []
    for q in range(chunks):
        recv = None
        for s in range(1, p):
            with trace.scope("ring_rs", name, f"hop{s - 1}"):
                j = (idx - s) % p
                with trace.scope("gemm", None, f"chunk{q}"):
                    g = mm(j * block_w + q * m, m)
                part = g if recv is None else recv + g
                recv = lax.ppermute(part, axn, perm)
        with trace.scope("ring_rs", name, "local"):
            g = mm(idx * block_w + q * m, m)
            outs.append(g if recv is None else recv + g)
    return outs[0] if chunks == 1 else jnp.concatenate(outs, axis=-1)


def ring_all_reduce_mm(name: AxisRef, mm: Callable, *, out_w: int,
                       dtype, chunks: int = 1):
    """Fused ``psum(full_mm_output, name)`` where the output is produced
    chunk by chunk, just in time for its reduce-scatter hop, then rebuilt
    by an all-gather ring (the decomposed activation all-reduce, AxoNN
    arXiv:2110.13005).

    ``mm(start, width) -> fp32 (..., width)`` computes this rank's
    partial for slice ``[start, start+width)`` of the reduced dim;
    ``out_w`` is that dim's full width. Partials are summed in fp32
    across the scatter ring and cast to ``dtype`` before the (pure data
    movement) gather ring, mirroring the blocking GEMM→cast→psum order.
    p == 2 takes the bidirectional-exchange fast path (one full-width
    GEMM + one hop each way; bitwise psum — two-term fp addition
    commutes); rings that do not split ``out_w`` evenly fall back to the
    blocking psum.
    """
    p, axn = flat_ring_axis(name)
    if p == 1:
        return mm(jnp.int32(0), out_w).astype(dtype)
    if p == 2:
        with trace.scope("ring_ar", name, "exchange"):
            y = mm(jnp.int32(0), out_w).astype(dtype)
            return y + lax.ppermute(y, axn, _ring_perm(2))
    if out_w % p:
        return jax.lax.psum(mm(jnp.int32(0), out_w).astype(dtype), name)
    with trace.scope("ring_ar", name):
        scat = ring_reduce_scatter_mm(name, mm, block_w=out_w // p,
                                      chunks=chunks).astype(dtype)
        return ring_all_gather(scat, name, dim=-1)


# ---------------------------------------------------------------------- #
# concrete overlapped primitives (called from core/parallel.py)
# ---------------------------------------------------------------------- #

def ag_matmul(x, w, name: AxisRef, *, chunks: int = 1):
    """``x @ AG_name(w, dim=1)`` (fwd of tp_matmul), ring-overlapped.

    x (..., k); w (k, n_loc). Returns (..., p*n_loc) in x.dtype."""
    def mm(wb):
        return lax.dot_general(
            x, wb, (((x.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(x.dtype)
    return ring_place(w, name, mm, gdim=1, chunks=chunks)


def ag_matmul_batched(x, w, name: AxisRef, *, chunks: int = 1):
    """Per-expert fwd: x (E, C, k) @ AG_name(w (E, k, n_loc), dim=2)."""
    def mm(wb):
        return lax.dot_general(
            x, wb, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32).astype(x.dtype)
    return ring_place(w, name, mm, gdim=2, chunks=chunks)


def accum_matmul_dx(dy, w, name: AxisRef, *, chunks: int = 1):
    """``dy @ AG_name(w, dim=1)^T`` (bwd dX of tp_matmul) without
    materializing the gathered weight. Returns fp32 (..., k)."""
    def mm(seg, wb):
        return lax.dot_general(
            seg, wb, (((seg.ndim - 1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
    return ring_accumulate(dy, w, name, mm, gdim=1, chunks=chunks)


def accum_matmul_dx_batched(dy, w, name: AxisRef, *, chunks: int = 1):
    """Per-expert bwd dX: dy (E, C, n_use) x w (E, k, n_loc). fp32."""
    def mm(seg, wb):
        return lax.dot_general(
            seg, wb, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
    return ring_accumulate(dy, w, name, mm, gdim=2, chunks=chunks)


def rs_matmul_dw(x2d, dy2d, name: AxisRef, *, block_w: int, chunks: int = 1):
    """``RS_name(x^T @ dy, dim=1)`` (bwd dW of tp_matmul) fused: each
    rank's (k, block) GEMM slice is computed as the ring partial for that
    block passes through. x2d (T, k); dy2d (T, n_use). Returns fp32
    (k, block_w)."""
    def mm(start, width):
        seg = lax.dynamic_slice_in_dim(dy2d, start, width, axis=1)
        return lax.dot_general(
            x2d, seg, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    return ring_reduce_scatter_mm(name, mm, block_w=block_w, chunks=chunks)


def rs_matmul_dw_batched(x, dy, name: AxisRef, *, block_w: int,
                         chunks: int = 1):
    """Per-expert bwd dW: RS over dim 2 of x (E,C,k)^T @ dy (E,C,n_use)."""
    def mm(start, width):
        seg = lax.dynamic_slice_in_dim(dy, start, width, axis=2)
        return lax.dot_general(
            x, seg, (((1,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
    return ring_reduce_scatter_mm(name, mm, block_w=block_w, chunks=chunks)


def accum_matmul_tied(h, table, name: AxisRef, *, chunks: int = 1):
    """Tied LM head fwd: ``h @ AG_name(table, dim=1)^T`` — the gathered
    dim is the contraction (d) dim. h (..., d/x); table (V/y, d_loc).
    Returns fp32 (..., V/y)."""
    def mm(seg, tb):
        return lax.dot_general(
            seg, tb, (((seg.ndim - 1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
    return ring_accumulate(h, table, name, mm, gdim=1, chunks=chunks)


def ag_matmul_tied_dh(dlogits, table, name: AxisRef, *, chunks: int = 1):
    """Tied LM head bwd dh: ``dlogits @ AG_name(table, dim=1)`` — the
    gathered dim is the *output* (d) dim. Returns (..., d/x) fp32."""
    def mm(tb):
        return lax.dot_general(
            dlogits, tb, (((dlogits.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    return ring_place(table, name, mm, gdim=1, chunks=chunks)


def rs_matmul_tied_dt(dl2d, h2d, name: AxisRef, *, block_w: int,
                      chunks: int = 1):
    """Tied LM head bwd dtable: ``RS_name(dlogits^T @ h, dim=1)`` fused.
    dl2d (T, V/y); h2d (T, d/x). Returns fp32 (V/y, block_w)."""
    def mm(start, width):
        seg = lax.dynamic_slice_in_dim(h2d, start, width, axis=1)
        return lax.dot_general(
            dl2d, seg, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    return ring_reduce_scatter_mm(name, mm, block_w=block_w, chunks=chunks)


# ---------------------------------------------------------------------- #
# decomposed x/y activation all-reduces (called from core/parallel.py)
# ---------------------------------------------------------------------- #

def ar_matmul(x, w, name: AxisRef, *, chunks: int = 1):
    """``psum_name(x @ w)`` (fwd of tp_matmul / tied-head bwd dh) with the
    activation all-reduce decomposed into a fused RS-matmul ring + AG
    ring: the GEMM produces each output slice just in time for its
    reduce-scatter hop. x (..., c); w (c, n). Returns (..., n), x.dtype."""
    def mm(start, width):
        wseg = lax.dynamic_slice_in_dim(w, start, width, axis=1)
        return lax.dot_general(
            x, wseg, (((x.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    return ring_all_reduce_mm(name, mm, out_w=w.shape[1], dtype=x.dtype,
                              chunks=chunks)


def ar_matmul_t(x, w, name: AxisRef, *, chunks: int = 1):
    """``psum_name(x @ w^T)`` — transposed rhs: the reduced output dim
    indexes ``w``'s *rows* (bwd dX of tp_matmul against the gathered
    weight; fwd of the tied head against the embedding table).
    x (..., c); w (n, c). Returns (..., n), x.dtype."""
    def mm(start, width):
        wseg = lax.dynamic_slice_in_dim(w, start, width, axis=0)
        return lax.dot_general(
            x, wseg, (((x.ndim - 1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
    return ring_all_reduce_mm(name, mm, out_w=w.shape[0], dtype=x.dtype,
                              chunks=chunks)


def ar_matmul_batched(x, w, name: AxisRef, *, chunks: int = 1):
    """Per-expert ``psum_name(x @ w)``: x (E, C, c); w (E, c, n).
    Returns (E, C, n), x.dtype."""
    def mm(start, width):
        wseg = lax.dynamic_slice_in_dim(w, start, width, axis=2)
        return lax.dot_general(
            x, wseg, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
    return ring_all_reduce_mm(name, mm, out_w=w.shape[2], dtype=x.dtype,
                              chunks=chunks)


def ar_matmul_batched_t(x, w, name: AxisRef, *, chunks: int = 1):
    """Per-expert ``psum_name(x @ w^T)`` (bwd dX of tp_batched_matmul):
    x (E, C, c); w (E, n, c) -- i.e. the gathered weight contracted over
    its last dim. Returns (E, C, n), x.dtype."""
    def mm(start, width):
        wseg = lax.dynamic_slice_in_dim(w, start, width, axis=1)
        return lax.dot_general(
            x, wseg, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
    return ring_all_reduce_mm(name, mm, out_w=w.shape[1], dtype=x.dtype,
                              chunks=chunks)


# ---------------------------------------------------------------------- #
# expert-axis a2a (called from layers/moe.py)
# ---------------------------------------------------------------------- #

def ring_a2a_expert(buf, name: AxisRef, ffn: Callable):
    """MoE dispatch → expert FFN → combine with both expert-axis
    all-to-alls decomposed into pairwise ``ppermute`` exchanges
    interleaved with the per-source expert GEMMs.

    ``buf`` (p, ...) is the dispatch buffer, dim 0 indexed by destination
    expert-rank; ``ffn(block) -> block`` applies this rank's local expert
    bank to one source rank's token block. Returns ``out`` with
    ``out[j]`` = rank j's experts' output for ``buf[j]`` — the layout the
    blocking ``a2a → ffn → a2a`` round trip produces, block for block.
    Each block crosses the wire exactly once each way (same wire bytes as
    the two blocking all-to-alls: 2·(p-1)/p of the buffer), so the result
    is bitwise identical; the p-1 exchange pairs are mutually
    data-independent, which is what lets XLA's latency-hiding scheduler
    ride shift s+1's permutes under shift s's GEMMs. Lowers to
    collective-permutes only — zero all-to-all HLO ops.
    """
    p, axn = flat_ring_axis(name)
    if buf.shape[0] != p:
        raise ValueError(
            f"dispatch buffer dim 0 ({buf.shape[0]}) must equal the "
            f"expert-axis ring size ({p})")
    if p == 1:
        return ffn(buf[0])[None]
    idx = flat_ring_index(name)
    # shift 0: this rank's own block never crosses the wire
    with trace.scope("ring_a2a", name, "local"):
        own = ffn(lax.dynamic_index_in_dim(buf, idx, axis=0,
                                           keepdims=False))
    out = jnp.zeros(buf.shape, own.dtype)
    out = lax.dynamic_update_index_in_dim(out, own, idx, axis=0)
    for s in range(1, p):
        with trace.scope("ring_a2a", name, f"shift{s}"):
            dst = (idx + s) % p
            send = lax.dynamic_index_in_dim(buf, dst, axis=0,
                                            keepdims=False)
            recv = lax.ppermute(send, axn, _ring_perm(p, s))
            y = ffn(recv)
            back = lax.ppermute(y.astype(out.dtype), axn,
                                _ring_perm(p, p - s))
            out = lax.dynamic_update_index_in_dim(out, back, dst, axis=0)
    return out
