"""The system under test, as the benchmark reaches it: the program's
configuration of a cell, its mesh, and the benchmark's weights placed in
the program's layout. Nothing here measures or judges."""
from __future__ import annotations

import sys

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from bench import weights as W
from bench.common import ROOT, family

if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))

dtype = W.dtype


def arch(c: dict):
    """The program's ``ArchConfig`` for a configuration file, by its
    family."""
    return family(c).arch(c)


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
    made = jax.eval_shape(
        lambda k: family(c).to_program(W.canonical(c, k), c),
        jax.random.PRNGKey(0))
    W.check_layout(made, structs)
    return structs, spec_tree_to_pspecs(specs)


def named(mesh_, pspec_tree):
    """A PartitionSpec tree -> a NamedSharding tree on ``mesh_``."""
    return jax.tree.map(lambda s: NamedSharding(mesh_, s), pspec_tree,
                        is_leaf=lambda x: isinstance(x, P))
