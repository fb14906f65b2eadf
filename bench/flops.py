"""Model operations per token, and the chips' peaks.

Each family counts its own (``train_flops_per_token`` in
``bench/families/<family>.py``) by these rules. Training counts what
the forward and backward passes require, 3 x the forward (the backward
is twice the forward), and no recompute:

* the weight matrices of every layer and of the output head, at 2 FLOPs
  per weight per token forward (6 with the backward);
* causal attention, QK^T and PV: a query at position i meets i + 1 keys,
  so over a sequence of S a token averages (S + 1) / 2 keys, and the two
  products cost 2 * 2 * heads * head_dim * (S + 1) / 2 forward;
* not the input-embedding lookup, which is a gather and no product.

``repro.core.comm_model.model_flops_per_token`` (6 x ``param_count``)
counts the embedding table as matmul FLOPs and leaves attention out;
this count is the benchmark's own.
"""
from __future__ import annotations

from bench.common import BENCH, load_json


def peak(device_kind: str) -> dict:
    """The published peaks of a device kind; an unknown kind is an
    error, never a default."""
    table = load_json(BENCH / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no published peak for {device_kind!r} in "
                       f"bench/peaks.json")
    return table[device_kind]
