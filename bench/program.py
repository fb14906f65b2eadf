"""The system under test, as the benchmark reaches it: the program's
configuration of a cell, its mesh, and the benchmark's weights placed in
the program's layout. Nothing here measures or judges."""
from __future__ import annotations

import dataclasses
import sys

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from bench import weights as W
from bench.common import ROOT

if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))

#: The program's activations, by the configuration file's name.
_ACT = {"silu": "silu", "gelu_new": "gelu", "gelu_pytorch_tanh": "gelu"}
#: The program's fixed norm epsilons.
_EPS = {"layernorm": 1e-5, "rmsnorm": 1e-6}
dtype = W.dtype


def arch(c: dict):
    """The program's ``ArchConfig`` for a configuration file: its
    registered config with every size the file states put in."""
    from repro.configs import get_config
    z = W.dims(c)
    if c.get("use_qkv_bias"):
        raise ValueError("the program has no attention with q/k/v biases "
                         "only")
    if z["act"] not in _ACT:
        raise ValueError(f"activation {z['act']!r} is not in the program")
    if _EPS[z["norm"]] != z["eps"]:
        raise ValueError(f"the program's {z['norm']} uses eps "
                         f"{_EPS[z['norm']]}, the configuration {z['eps']}")
    return dataclasses.replace(
        get_config(c["program_config"]), n_layers=z["L"], d_model=z["d"],
        n_heads=z["H"], n_kv_heads=z["KV"], head_dim=z["hd"], d_ff=z["F"],
        vocab_size=z["V"], norm=z["norm"], act=_ACT[z["act"]],
        gated_mlp=z["gated"], rotary_pct=z["rot"], rope_theta=z["theta"],
        tie_embeddings=z["tied"], attn_bias=False, qk_norm=False,
        sliding_window=0, moe=None, mla=None, mixer_pattern=(),
        ffn_pattern=(), mtp_depth=0, mamba=None, xlstm=None, encoder=None,
        arch_type="dense")


def mesh(factors, devices):
    from repro.launch import mesh as LM
    return LM.MeshLifecycle(*factors, devices=devices).build()


def param_layout(c: dict, axes):
    """(abstract structs, PartitionSpecs) of the program's parameters,
    checked against the tree the benchmark makes."""
    from repro.core.partition import spec_tree_to_pspecs
    from repro.launch import steps as ST
    structs, specs = ST.init_model(arch(c), axes, abstract=True,
                                   dtype=dtype(c))
    made = jax.eval_shape(lambda k: W.to_program(W.canonical(c, k), c),
                          jax.random.PRNGKey(0))
    W.check_layout(made, structs)
    return structs, spec_tree_to_pspecs(specs)


def named(mesh_, pspec_tree):
    """A PartitionSpec tree -> a NamedSharding tree on ``mesh_``."""
    return jax.tree.map(lambda s: NamedSharding(mesh_, s), pspec_tree,
                        is_leaf=lambda x: isinstance(x, P))
