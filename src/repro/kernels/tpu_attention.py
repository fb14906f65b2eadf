"""The attention core of training and prefill on the TPU: the Pallas flash
kernel that JAX ships (``jax.experimental.pallas.ops.tpu.flash_attention``).

The kernel streams K/V blocks through an online softmax whose statistics
(m, l) and accumulator stay in fp32, feeds the MXU the activations' own
bf16 operands with fp32 accumulation, skips the blocks a causal mask
covers whole, and has its own Pallas backward (a dK/dV kernel and a dQ
kernel). So no (T, T) score tensor reaches device memory, forward,
remat recompute or backward.

JAX's splash attention kernel measured faster at the benchmark's shapes
(PERF.md), but it hands its block sizes to ``pallas_call`` as
``metadata``, and a custom call lowered with that metadata carries no
``op_name``: its device time would read as no layer's. This kernel's
calls keep the caller's ``jax.named_scope`` names, forward and backward.

``layers.attention.attn_core`` calls :func:`flash_mha` when
:func:`supports` holds and the program is lowered for a TPU; every other
call keeps the jnp core, which is also this kernel's test oracle.
"""
from __future__ import annotations

import math

import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu import flash_attention as FA

#: The kernel's smallest block along the sequence (its lane width).
MIN_BLOCK = FA.MIN_BLOCK_SIZE
#: The largest block along the sequence, by the kernel's head dim
#: (:func:`kernel_dim`): the block rule (PERF.md's sweeps on a TPU v5e).
#: A head dim not here is not tiled. 256 runs MLA's 192-wide q/k head
#: (DeepSeek-V2: 128 nope + 64 rope).
MAX_BLOCK = {64: 1024, 128: 512, 256: 512}


def kernel_dim(d: int) -> int:
    """The head dim the kernel runs for head dim ``d``: ``d`` up to 128,
    else the next multiple of 128 (the kernel takes a wider head only in
    whole 128 lanes); q, k and v are zero-padded to it."""
    return d if d <= 128 else -(-d // 128) * 128


def block_size(T: int, d: int) -> int:
    """The sequence block of every forward and backward tile at length
    ``T`` and head dim ``d``: the largest power of two from
    :data:`MIN_BLOCK` up to ``MAX_BLOCK[kernel_dim(d)]`` that divides
    ``T``; 0 where none does (``T`` not a multiple of :data:`MIN_BLOCK`,
    or the kernel's head dim not in :data:`MAX_BLOCK`)."""
    b = MAX_BLOCK.get(kernel_dim(d), 0)
    while b >= MIN_BLOCK:
        if b <= T and T % b == 0:
            return b
        b //= 2
    return 0


def supports(q, k, v, *, window: int = 0, q_pos0=0, scale=None) -> bool:
    """Whether :func:`flash_mha` computes what ``attn_core`` computes for
    these arguments: self-attention from position 0 (``Tq == Tk``), no
    sliding window, one head dim for q and k and at most that for v (MLA's
    v is narrower, and its q/k head no multiple of 128: :func:`flash_mha`
    pads them), as many K/V heads as q heads, bf16 operands (the kernel's
    dots run on its operands' dtype, and the jnp core's fp32 dots under a
    raised ``default_matmul_precision`` would be more exact), and a length
    and head dim that :func:`block_size` tiles; any scale."""
    T, d = q.shape[1], q.shape[3]
    return (window == 0
            and isinstance(q_pos0, int) and q_pos0 == 0
            and k.shape == q.shape and v.shape[:3] == q.shape[:3]
            and v.shape[3] <= d
            and all(x.dtype == jnp.bfloat16 for x in (q, k, v))
            and block_size(T, d) > 0)


def flash_mha(q, k, v, *, causal: bool, scale=None):
    """(B, T, H, d) q and k, (B, T, H, dv) v with dv <= d -> (B, T, H, dv)
    in q's dtype, with scores scaled by ``scale`` (default 1/sqrt(d)).
    q, k and v run zero-padded to :func:`kernel_dim` and the output is
    sliced back: the padded q/k columns add nothing to the scores, the
    padded v columns give zero outputs, and the gradients of the padding
    are dropped. Differentiable: the kernel's own custom VJP."""
    T, d = q.shape[1], q.shape[3]
    dv, dk = v.shape[3], kernel_dim(d)

    def pad(x):
        w = dk - x.shape[3]
        return jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, w))) if w else x
    b = block_size(T, d)
    blocks = FA.BlockSizes(
        block_q=b, block_k_major=b, block_k=b, block_b=1,
        block_q_major_dkv=b, block_k_major_dkv=b, block_k_dkv=b,
        block_q_dkv=b, block_k_major_dq=b, block_k_dq=b, block_q_dq=b)
    out = FA.flash_attention(
        *(pad(x).swapaxes(1, 2) for x in (q, k, v)), causal=causal,
        sm_scale=1.0 / math.sqrt(d) if scale is None else scale,
        block_sizes=blocks)
    out = out.swapaxes(1, 2)
    if dv < dk:
        out = out[..., :dv]
    return out.astype(q.dtype)
