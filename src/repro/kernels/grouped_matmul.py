"""Grouped matrix products over the routed experts a chip holds.

The rows of ``x`` are sorted by expert: the first ``sizes[0]`` rows go to
expert 0, the next ``sizes[1]`` to expert 1, and so on. Each group is
multiplied by its own expert's matrix; the rows past ``sum(sizes)`` are
padding of a static buffer sized for the worst case and are not
computed. On the TPU the product is the Pallas ``megablox`` kernel that
JAX ships (its ``gmm``, with ``tgmm`` for the weight gradient, under its
own custom VJP), which visits only the tiles that hold routed rows;
elsewhere ``jax.lax.ragged_dot``, which is also the kernel's test oracle.
The choice is made at lowering (``lax.platform_dependent``), as for the
attention core (``kernels/tpu_attention``).

The kernel's calls keep the caller's ``jax.named_scope`` names (it passes
no ``metadata`` to ``pallas_call``), so the benchmark's layer map can
read their device time.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox import ops as MB

#: The kernel's (m, k, n) tile (PERF.md's sweep on a TPU v5e).
TILING = (512, 1024, 1024)


def _gmm(x, w, sizes):
    m = x.shape[0]
    tm = min(TILING[0], m)
    pad = -m % tm
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    out = MB.gmm(x, w, sizes, x.dtype, (tm, *TILING[1:]))
    return out[:m] if pad else out


def _ragged(x, w, sizes):
    return jax.lax.ragged_dot(x, w, sizes,
                              preferred_element_type=jnp.float32
                              ).astype(x.dtype)


def grouped_matmul(x, w, sizes):
    """(m, k) rows sorted by group, (g, k, n) weights, (g,) int32 group
    sizes summing to at most m -> (m, n) in ``x``'s dtype, accumulated in
    float32. The rows past ``sum(sizes)`` hold unspecified values: the
    caller masks them, forward and backward."""
    return jax.lax.platform_dependent(x, w, sizes, tpu=_gmm,
                                      default=_ragged)
