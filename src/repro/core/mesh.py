"""Mesh axis conventions for the 4D hybrid algorithm.

The paper decomposes ``G`` devices as ``G_data x G_x x G_y x G_z``:

  * ``data`` — data parallelism (batch sharding; may include a leading
    ``pod`` axis on multi-pod meshes, since pods simply extend data
    parallelism),
  * ``x``    — tensor-parallel rows: shards the *contraction* (k) dim of a
    "normal" layer's weight and the feature dim of the residual stream,
  * ``y``    — tensor-parallel columns: shards the output (n) dim of a
    normal layer; activations are replicated over ``y``,
  * ``z``    — depth: co-shards the batch and the weight/optimizer storage
    (weights all-gathered over ``z`` at use, gradients reduce-scattered),
  * ``seq``  — context parallelism: shards the *sequence* (token) dim of
    activations in a striped layout (seq-rank r holds global positions
    r, r+p, r+2p, ... — the causal load-balancing stripe); weights stay
    replicated over ``seq`` and attention runs as a KV ``ppermute`` ring
    (layers/attention.py),
  * ``expert`` — expert parallelism: shards the routed-expert bank of
    MoE layers (layers/moe.py) AND the batch dim (dense layers see it as
    a second data axis); tokens cross it via the capacity-based
    dispatch/combine all-to-all, ring-decomposed into pairwise
    ``ppermute`` exchanges when ``OverlapConfig.expert_a2a`` is on
    (core/collective_matmul.py).

Setting ``z=None`` (G_z=1) recovers the supplied Tensor3D text verbatim;
setting additionally ``y=None`` recovers Megatron-LM 1D tensor
parallelism. ``seq=None`` (G_seq=1, the default) recovers the 4D model
of PRs 1-5 bitwise, and ``expert=None`` (G_expert=1, the default) the
5-axis model of PRs 6-9 bitwise.

Everything in :mod:`repro.layers` is written against :class:`MeshAxes`, so
the same model code runs on the assignment-mandated ``("data","model")``
production mesh (1D TP baseline) and on the 4D mesh.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import trace
from repro.core.overlap import OverlapConfig

AxisName = Union[str, Tuple[str, ...], None]


def _names(axis: AxisName) -> Tuple[str, ...]:
    if axis is None:
        return ()
    if isinstance(axis, str):
        return (axis,)
    return tuple(axis)


@dataclasses.dataclass(frozen=True)
class MeshAxes:
    """Logical 4D axes bound to physical mesh axis names (or None == size 1)."""

    data: AxisName = ("data",)
    x: AxisName = "x"
    y: AxisName = "y"
    z: AxisName = "z"
    # context parallelism (None == unsharded sequence, the 4D model)
    seq: AxisName = None
    # expert parallelism (None == experts sharded over y only, the
    # 5-axis model)
    expert: AxisName = None
    # static sizes, captured from the physical mesh at bind time
    sizes: Tuple[Tuple[str, int], ...] = ()
    # comm/compute-overlap knobs for the tp primitives (core/overlap.py);
    # rides here so layers don't thread an extra argument everywhere
    overlap: OverlapConfig = OverlapConfig()

    # ------------------------------------------------------------------ #
    def size(self, axis: AxisName) -> int:
        d = dict(self.sizes)
        return math.prod(d.get(n, 1) for n in _names(axis))

    @property
    def dp(self) -> int:
        return self.size(self.data)

    @property
    def gx(self) -> int:
        return self.size(self.x)

    @property
    def gy(self) -> int:
        return self.size(self.y)

    @property
    def gz(self) -> int:
        return self.size(self.z)

    @property
    def gseq(self) -> int:
        return self.size(self.seq)

    @property
    def gexpert(self) -> int:
        return self.size(self.expert)

    @property
    def tensor(self) -> int:
        return self.gx * self.gy * self.gz

    @property
    def batch_shards(self) -> int:
        """How many ways the global batch is split (data x z x expert)."""
        return self.dp * self.gz * self.gexpert

    @property
    def token_shards(self) -> int:
        """How many ways the token grid (batch x seq) is split."""
        return self.batch_shards * self.gseq

    def axis(self, logical: str) -> AxisName:
        return {"data": self.data, "x": self.x, "y": self.y, "z": self.z,
                "seq": self.seq, "expert": self.expert}[logical]

    def all_names(self) -> Tuple[str, ...]:
        out: Tuple[str, ...] = ()
        for a in (self.data, self.x, self.y, self.z, self.seq, self.expert):
            out += _names(a)
        return out

    def batch_axes(self) -> Tuple[str, ...]:
        """Mesh axes the batch dim is sharded over (data, z, then expert
        — dense layers see the expert axis as a second data axis; MoE
        layers re-gather its tokens via the dispatch all-to-all)."""
        return _names(self.data) + _names(self.z) + _names(self.expert)

    def token_axes(self) -> Tuple[str, ...]:
        """Mesh axes the token grid is sharded over (batch + seq) — the
        reduction set for per-token sums like the LM loss."""
        return self.batch_axes() + _names(self.seq)

    def swap_xy(self) -> "MeshAxes":
        return dataclasses.replace(self, x=self.y, y=self.x)

    def with_overlap(self, overlap: OverlapConfig) -> "MeshAxes":
        return dataclasses.replace(self, overlap=overlap)

    # -- PartitionSpec helpers ---------------------------------------- #
    def pspec(self, *dims: AxisName) -> P:
        """Build a PartitionSpec from per-dim logical axis names."""
        out = []
        for d in dims:
            n = _names(d)
            if not n:
                out.append(None)
            elif len(n) == 1:
                out.append(n[0])
            else:
                out.append(n)
        return P(*out)


def bind_axes(mesh: Mesh, *, data: AxisName, x: AxisName = None,
              y: AxisName = None, z: AxisName = None,
              seq: AxisName = None, expert: AxisName = None) -> MeshAxes:
    """Bind logical 4D axes to a physical mesh, validating names.

    Tuple axes must list their names in mesh-axis order: the flattened
    ring helpers (:func:`flat_ring_axis`) and ``lax.ppermute``'s group
    numbering (sorted global device ids == mesh order) agree only then —
    out-of-order tuples would silently route ring hops to the wrong
    ranks."""
    sizes = tuple(zip(mesh.axis_names, mesh.devices.shape))
    known = dict(sizes)
    order = {name: i for i, name in enumerate(mesh.axis_names)}
    for a in (data, x, y, z, seq, expert):
        n = _names(a)
        for name in n:
            if name not in known:
                raise ValueError(
                    f"axis {name!r} not in mesh axes {mesh.axis_names}")
        pos = [order[name] for name in n]
        if pos != sorted(pos):
            raise ValueError(
                f"tuple axis {n!r} must list names in mesh-axis order "
                f"{mesh.axis_names} (ring collectives linearize by it)")
    return MeshAxes(data=data, x=x, y=y, z=z, seq=seq, expert=expert,
                    sizes=sizes)


# ---------------------------------------------------------------------- #
# Collective helpers that degrade to identity when the axis is unmapped.
# These are only legal inside shard_map bodies.
# ---------------------------------------------------------------------- #

def psum(v, axis: AxisName):
    n = _names(axis)
    return jax.lax.psum(v, n) if n else v


def pmax(v, axis: AxisName):
    n = _names(axis)
    return jax.lax.pmax(v, n) if n else v


def all_gather(v, axis: AxisName, *, dim: int, tiled: bool = True):
    """Tiled all-gather; tuple axes gather minor name first so the result
    blocks land FIRST-name-major — the order a PartitionSpec tuple shards
    the global dim, and the flattened-ring layout of the ring helpers."""
    n = _names(axis)
    if not n:
        return v
    dim = dim % v.ndim  # lax collectives reject negative dims
    out = v
    for name in reversed(n):
        out = jax.lax.all_gather(out, name, axis=dim, tiled=tiled)
    return out


def psum_scatter(v, axis: AxisName, *, dim: int, tiled: bool = True):
    """Tiled reduce-scatter; tuple axes scatter major name first (the
    exact inverse of :func:`all_gather`'s first-name-major layout)."""
    n = _names(axis)
    if not n:
        return v
    dim = dim % v.ndim  # lax collectives reject negative dims
    out = v
    for name in n:
        out = jax.lax.psum_scatter(out, name, scatter_dimension=dim, tiled=tiled)
    return out


def ring_perm(p: int, shift: int = 1):
    """The send-right ring permutation (rank i -> i + shift mod p).

    Single source of the ring convention shared by the helpers below and
    the fused drivers in core/collective_matmul.py: after ``s`` hops rank
    ``i`` holds the block originally owned by rank ``(i - s) mod p``."""
    return [(i, (i + shift) % p) for i in range(p)]


def flat_ring_axis(axis: AxisName):
    """(p, ppermute axis arg) of the flattened ring over ``axis``.

    Multi-name axes form ONE ring over the FIRST-name-major
    linearization — the order a PartitionSpec tuple shards a dim, and
    (since ``lax.ppermute`` numbers a multi-name group by sorted global
    device id, i.e. by mesh-axis order) the order the permutation indices
    actually route, provided the tuple lists its names in mesh-axis
    order — which every :class:`MeshAxes` binding does. The blocking
    :func:`all_gather` / :func:`psum_scatter` helpers produce the same
    layout, so ring and blocking schedules stay interchangeable."""
    n = _names(axis)
    p = math.prod(jax.lax.axis_size(name) for name in n)
    return p, (n if len(n) > 1 else n[0])


def flat_ring_index(axis: AxisName):
    """This rank's position on the flattened (first-name-major) ring."""
    return axis_index(axis)


def ppermute_ring(v, axis: AxisName, shift: int = 1):
    """One ring hop: send to (i + shift) mod p along ``axis``.

    Identity on unmapped axes. Multi-name axes hop along the flattened
    ring of :func:`flat_ring_axis`.
    """
    n = _names(axis)
    if not n:
        return v
    p, axn = flat_ring_axis(axis)
    if p == 1:
        return v
    return jax.lax.ppermute(v, axn, ring_perm(p, shift))


def ring_all_gather(v, axis: AxisName, *, dim: int):
    """``all_gather(tiled=True)`` decomposed into p-1 ``ppermute`` ring
    steps (so XLA can overlap each hop with unrelated compute). Bitwise
    the same result ordering as :func:`all_gather` (tuple axes ring once
    over the flattened group instead of once per name — same layout,
    fewer chained rings); identity on unmapped axes."""
    n = _names(axis)
    if not n:
        return v
    p, axn = flat_ring_axis(axis)
    if p == 1:
        return v
    dim = dim % v.ndim
    idx = flat_ring_index(axis)
    perm = ring_perm(p)
    chunk = v.shape[dim]
    out_shape = list(v.shape)
    out_shape[dim] = p * chunk
    out = jnp.zeros(tuple(out_shape), v.dtype)
    cur = v
    for s in range(p):
        with trace.scope("ring_ag", axis, f"hop{s}"):
            # after s hops of the send-right ring, we hold rank
            # (i - s)'s block
            j = (idx - s) % p
            out = jax.lax.dynamic_update_slice_in_dim(out, cur, j * chunk,
                                                      axis=dim)
            if s < p - 1:
                cur = jax.lax.ppermute(cur, axn, perm)
    return out


def ring_reduce_scatter(v, axis: AxisName, *, dim: int):
    """``psum_scatter(tiled=True)`` as a p-1 step ``ppermute`` ring:
    each rank's partial for block j is added just-in-time as the running
    sum passes through. Identity on unmapped axes; tuple axes ring once
    over the flattened group (same block layout as the per-name loop in
    :func:`psum_scatter`)."""
    n = _names(axis)
    if not n:
        return v
    p, axn = flat_ring_axis(axis)
    if p == 1:
        return v
    dim = dim % v.ndim
    if v.shape[dim] % p:
        raise ValueError(  # psum_scatter(tiled=True) rejects this too
            f"ring_reduce_scatter: dim {dim} of size {v.shape[dim]} not "
            f"divisible by axis {n!r} size {p}")
    idx = flat_ring_index(axis)
    perm = ring_perm(p)
    chunk = v.shape[dim] // p
    recv = None
    for s in range(1, p):
        with trace.scope("ring_rs", axis, f"hop{s - 1}"):
            # the partial destined for rank (i - s) leaves here at step s
            j = (idx - s) % p
            g = jax.lax.dynamic_slice_in_dim(v, j * chunk, chunk, axis=dim)
            part = g if recv is None else recv + g
            recv = jax.lax.ppermute(part, axn, perm)
    with trace.scope("ring_rs", axis, "local"):
        g = jax.lax.dynamic_slice_in_dim(v, idx * chunk, chunk, axis=dim)
        return g if recv is None else recv + g


def ring_all_reduce(v, axis: AxisName, *, dim: int = -1):
    """:func:`psum` decomposed into a reduce-scatter ring phase followed
    by an all-gather ring phase over ``dim`` (the bandwidth-optimal
    all-reduce, spelled as 2(p-1) ``ppermute`` hops so XLA's
    latency-hiding scheduler can interleave them with unrelated compute).

    Fast path p == 2: the send-right "ring" *is* the bidirectional
    exchange — each shard sends its full buffer one hop and adds what it
    receives (bitwise equal to psum: two-term fp addition commutes).
    Identity on unmapped/size-1 axes; falls back to the blocking psum
    when ``dim`` does not split evenly over the ring (the scatter phase
    needs equal blocks). Results match psum within fp32-accumulation
    reassociation; exactly when the addends sum exactly."""
    n = _names(axis)
    if not n:
        return v
    p, axn = flat_ring_axis(axis)
    if p == 1:
        return v
    if p == 2:
        with trace.scope("ring_ar", axis, "exchange"):
            return v + jax.lax.ppermute(v, axn, ring_perm(2))
    dim = dim % v.ndim
    if v.shape[dim] % p:
        return jax.lax.psum(v, n)
    with trace.scope("ring_ar", axis):
        return ring_all_gather(ring_reduce_scatter(v, axis, dim=dim), axis,
                               dim=dim)


def all_to_all(v, axis: AxisName, *, dim: int = 0):
    """Blocking all-to-all over ``axis``: ``dim`` (p equal blocks, block
    k destined for rank k) is exchanged so the result's block k holds
    what rank k sent here — the MoE dispatch/combine primitive
    (layers/moe.py). Identity on unmapped/size-1 axes."""
    n = _names(axis)
    if not n:
        return v
    p, axn = flat_ring_axis(axis)
    if p == 1:
        return v
    dim = dim % v.ndim
    with trace.scope("a2a", axis):
        return jax.lax.all_to_all(v, axn, split_axis=dim, concat_axis=dim,
                                  tiled=True)


def ring_all_to_all(v, axis: AxisName, *, dim: int = 0):
    """:func:`all_to_all` decomposed into p-1 pairwise ``ppermute``
    exchanges (shift s moves each rank's block destined s hops ahead
    directly there), so no all-to-all op reaches the HLO and XLA's
    latency-hiding scheduler can interleave the permutes with unrelated
    compute — the same schedule family as the z/AR rings. Bitwise the
    same block layout as the blocking path (each block travels exactly
    once either way); falls back to the blocking :func:`all_to_all` when
    ``dim`` does not split evenly over the group. Identity on
    unmapped/size-1 axes."""
    n = _names(axis)
    if not n:
        return v
    p, axn = flat_ring_axis(axis)
    if p == 1:
        return v
    dim = dim % v.ndim
    if v.shape[dim] % p:
        return all_to_all(v, axis, dim=dim)   # blocking fallback
    idx = flat_ring_index(axis)
    chunk = v.shape[dim] // p
    own = jax.lax.dynamic_slice_in_dim(v, idx * chunk, chunk, axis=dim)
    out = jnp.zeros_like(v)
    out = jax.lax.dynamic_update_slice_in_dim(out, own, idx * chunk,
                                              axis=dim)
    for s in range(1, p):
        with trace.scope("ring_a2a", axis, f"shift{s}"):
            send = jax.lax.dynamic_slice_in_dim(
                v, ((idx + s) % p) * chunk, chunk, axis=dim)
            recv = jax.lax.ppermute(send, axn, ring_perm(p, s))
            out = jax.lax.dynamic_update_slice_in_dim(
                out, recv, ((idx - s) % p) * chunk, axis=dim)
    return out


def stripe_seq(v, p: int, *, dim: int = 1):
    """Permute a global sequence dim into the striped context-parallel
    layout: contiguous shard r of the result holds global positions
    ``r, r + p, r + 2p, ...`` (``result[r*C + j] == v[j*p + r]`` with
    ``C = T // p``), so a plain ``PartitionSpec`` shard over the seq axis
    lands each rank the causal load-balancing stripe. Identity at p == 1.
    Runs OUTSIDE shard_map, on the global batch."""
    if p <= 1:
        return v
    dim = dim % v.ndim
    t = v.shape[dim]
    if t % p:
        raise ValueError(f"stripe_seq: dim {dim} of size {t} not "
                         f"divisible by g_seq {p}")
    shape = v.shape[:dim] + (t // p, p) + v.shape[dim + 1:]
    return jnp.swapaxes(v.reshape(shape), dim, dim + 1).reshape(v.shape)


def unstripe_seq(v, p: int, *, dim: int = 1):
    """Inverse of :func:`stripe_seq` (``result[j*p + r] == v[r*C + j]``)."""
    if p <= 1:
        return v
    dim = dim % v.ndim
    t = v.shape[dim]
    if t % p:
        raise ValueError(f"unstripe_seq: dim {dim} of size {t} not "
                         f"divisible by g_seq {p}")
    shape = v.shape[:dim] + (p, t // p) + v.shape[dim + 1:]
    return jnp.swapaxes(v.reshape(shape), dim, dim + 1).reshape(v.shape)


def axis_index(axis: AxisName):
    n = _names(axis)
    if not n:
        return jnp.int32(0)
    idx = jnp.int32(0)
    for name in n:
        idx = idx * jax.lax.axis_size(name) + jax.lax.axis_index(name)
    return idx


def axis_size_in(axes: MeshAxes, axis: AxisName) -> int:
    return axes.size(axis)


def named_sharding(mesh: Mesh, spec: P) -> NamedSharding:
    return NamedSharding(mesh, spec)
