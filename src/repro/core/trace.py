"""Named-scope trace attribution: which layer of the model, and which
ring schedule, an op of the compiled program belongs to.

A scope is a plain ``jax.named_scope``: its name lands in the
``metadata op_name`` of every op traced inside it (``…/attn/dot_general``,
``…/transpose(jvp(mlp))/…``), survives into the optimized HLO, and costs
nothing on the device: only metadata differs from an unscoped build
(tests/test_telemetry.py and tests/test_layer_scopes.py pin this).

Scopes are always on. There is no toggle, and there must not be one
while JAX's persistent compile cache ignores metadata: its key strips
debug info, so a build with scopes could load an executable compiled
without them and the attribution would silently disappear.

**Layer scopes** (:data:`LAYERS`) name where each layer's work happens;
they nest and the innermost wins (``attn_core`` inside ``attn``):

    vocab      embedding lookup; logits and vocab-parallel cross-entropy
    norm       every LayerNorm / RMSNorm (models/decoder.py _apply_norm)
    attn, mla, mamba, mlstm, slstm
               a block's mixer and its residual add (_block_apply)
    attn_core  the softmax core of attention: scores, softmax, PV
    mlp, moe   a block's ffn and its residual add (_block_apply)
    experts    inside moe: the routed experts' products (layers/moe.py);
               moe keeps the router, dispatch, combine, shared experts
    update     gradient accumulation, reductions, clip and the optimizer
               update (launch/steps.make_train_step)

**Ring scopes** (:func:`scope`) name the overlap schedules, which all
lower to anonymous ``collective-permute`` chains. Their names mirror the
``comm_model`` collective classes:

    ring_ag[z]/hop2          z weight all-gather ring, hop 2
    ring_rs[z]/hop0          z weight-grad reduce-scatter ring
    ring_ar[x]/exchange      x activation all-reduce (p=2 fast path)
    dp_rs/bucket3            DP gradient bucket 3's reduce-scatter
    zero3_ag[data]/leaf7     ZeRO-3 just-in-time gather of leaf 7
    ring_exchange[seq]/hop1  ring-attention KV circulation, hop 1
    embed_gather[z]          embedding-table z gather
"""
from __future__ import annotations

from typing import Optional, Sequence, Union

import jax

AxisLike = Union[None, str, Sequence[str]]

#: The layer scope names, the whole vocabulary a reader of ``op_name``
#: may attribute an op to.
LAYERS = ("vocab", "norm", "attn", "attn_core", "mla", "mamba", "mlstm",
          "slstm", "mlp", "moe", "experts", "update")


def _axis_str(axis: AxisLike) -> str:
    if axis is None:
        return ""
    if isinstance(axis, (tuple, list)):
        return "+".join(str(a) for a in axis)
    return str(axis)


def label(kind: str, axis: AxisLike = None, detail: Optional[str] = None
          ) -> str:
    """``kind[axis]/detail`` — the scope naming convention
    (docs/telemetry.md). ``axis`` may be a mesh axis name or a tuple of
    names (flattened rings render as ``a+b``); both parts optional."""
    name = kind
    s = _axis_str(axis)
    if s:
        name += f"[{s}]"
    if detail:
        name += f"/{detail}"
    return name


def scope(kind: str, axis: AxisLike = None, detail: Optional[str] = None):
    """Context manager / decorator naming everything traced inside it
    ``label(kind, axis, detail)``."""
    return jax.named_scope(label(kind, axis, detail))


def layer(name: str):
    """The scope of one of :data:`LAYERS`."""
    if name not in LAYERS:
        raise ValueError(f"no layer scope {name!r}; known: {LAYERS}")
    return jax.named_scope(name)
