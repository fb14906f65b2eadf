"""Weights made from the seed, on the device, in the type they run in.

The benchmark, not the program, makes the weights, so that the plain
reference can make the same ones from the same seed and take nothing that
the program has made. They are made in a canonical layout of the
benchmark's own (a flat dict of names, layers stacked on axis 0) that the
configuration's family defines; its ``to_program`` rearranges them into
the program's parameter tree inside the same jitted call, and
:func:`check_layout` checks that tree against the program's own abstract
one, so a change of layout in the program fails here loudly.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from bench.common import family

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def dtype(c: dict):
    """The type the configuration runs in (``torch_dtype``)."""
    return DTYPES[c["torch_dtype"]]


def canonical(c: dict, key) -> Dict[str, jax.Array]:
    """The canonical weights of ``key`` = ``common.base_key(seed)`` in the
    configuration's type (traceable; call under jit with the key as an
    argument, so that one compiled program serves every seed)."""
    dt = dtype(c)
    fam = family(c)
    shapes = sorted(fam.canonical_shapes(c).items())
    out = {}
    for i, (name, shape) in enumerate(shapes):
        out[name] = fam.init(name, jax.random.fold_in(key, i),
                             shape).astype(dt)
    return out


def program_names(c: dict) -> dict:
    """The program tree with each leaf replaced by its canonical name."""
    fam = family(c)
    return fam.to_program({k: k for k in fam.canonical_shapes(c)}, c)


def check_layout(tree_structs, abstract_structs) -> None:
    """Raise unless the made tree has the program's structure and shapes."""
    a = jax.tree_util.tree_structure(tree_structs)
    b = jax.tree_util.tree_structure(abstract_structs)
    if a != b:
        raise RuntimeError(f"the program's parameter tree changed:\n{b}\n"
                           f"the benchmark makes\n{a}")
    for x, y in zip(jax.tree.leaves(tree_structs),
                    jax.tree.leaves(abstract_structs)):
        if tuple(x.shape) != tuple(y.shape):
            raise RuntimeError(f"parameter shape {x.shape} != the "
                               f"program's {y.shape}")
