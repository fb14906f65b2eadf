"""Model families: found by file name alone, an unknown one refused, the
dense family refusing a registered config of another family, the seed's
dense weights bit for bit what they were before families, and no dense
leaf named outside the family files."""
import importlib.util
import re
import shutil

import jax
import numpy as np
import pytest

from bench import common as C
from bench import weights as W
from bench.common import base_key
from bench.drivers import train as DT
from bench.tests import tiny

SEEDS = (2**31 + 17, 2**40 + 3)
CONFIGS = ("stablelm-1.6b-6l", "gpt-paper-20b-2l")


def _config(name):
    return C.load_json(C.BENCH / "configs" / f"{name}.json")


def _frozen():
    path = C.BENCH / "tests" / "data" / "dense_weights_frozen.py"
    spec = importlib.util.spec_from_file_location("dense_weights_frozen",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _readings(cfg, seed):
    """The program's and the reference's three-step readings."""
    cell = tiny.cell("stablelm-1.6b.train-2k", cfg, {})
    devices = jax.devices()[:1]
    tr = DT.Trainer(cell, devices)
    prog = tr.start(seed, 3)
    ref = DT.reference_readings(cfg, cell.spec["optimizer"], seed,
                                tr.host_batches[:3], devices)
    return prog, ref


def _assert_same(a, b):
    np.testing.assert_array_equal(a["loss"], b["loss"])
    for k in ("grad", "change"):
        assert a[k].keys() == b[k].keys()
        for leaf in a[k]:
            np.testing.assert_array_equal(a[k][leaf], b[k][leaf])


def test_a_family_is_found_by_its_file_alone(tmp_path, monkeypatch):
    cfg = dict(tiny.config("layernorm-gated-mha-partial-rotary"),
               torch_dtype="float32")
    assert "family" not in cfg
    before = _readings(cfg, 2**31 + 23)
    shutil.copy(C.FAMILIES / "dense.py", tmp_path / "copied_dense.py")
    monkeypatch.setattr(C, "FAMILIES", tmp_path)
    with pytest.raises(ValueError, match="copied_dense"):
        C.family(cfg)        # no dense.py there: the directory is used
    after = _readings(dict(cfg, family="copied_dense"), 2**31 + 23)
    for a, b in zip(before, after):
        _assert_same(a, b)


def test_an_unknown_family_names_the_known_ones():
    with pytest.raises(ValueError, match="'dense'"):
        C.family({"family": "no-such-family"})


def test_dense_refuses_a_config_of_another_family():
    cfg = dict(_config("stablelm-1.6b-6l"),
               program_config="deepseek-v2-lite-16b")
    with pytest.raises(ValueError, match="moe, mla"):
        C.family(cfg).arch(cfg)


@pytest.mark.parametrize("name", CONFIGS)
def test_dense_leaves_are_as_before(name):
    """Names, sorted order (each name's ``fold_in`` index), shapes and
    type of both configurations, compared without making them."""
    old, cfg = _frozen(), _config(name)
    new = C.family(cfg).canonical_shapes(cfg)
    assert sorted(new.items()) == sorted(old.canonical_shapes(cfg).items())
    assert W.dtype(cfg) == old.dtype(cfg)


@pytest.mark.parametrize("variant", [v for f, v, _ in tiny.variants()
                                     if f == "dense"])
def test_dense_weights_are_bit_for_bit_as_before(variant):
    """The values, from two large seeds, in each kind of dense layer."""
    old, cfg = _frozen(), tiny.config(variant)
    for seed in SEEDS:
        key = base_key(seed)
        new = jax.jit(lambda k: W.canonical(cfg, k))(key)
        ref = jax.jit(lambda k: old.canonical(cfg, k))(key)
        assert list(new) == list(ref)
        for k in ref:
            assert new[k].dtype == ref[k].dtype
            assert np.asarray(new[k]).tobytes() == \
                np.asarray(ref[k]).tobytes(), (seed, k)


def test_no_dense_leaf_outside_the_families():
    dense_words = re.compile(r"\b(wq|wo_mlp|norm1|lm_head)\b|"
                             r"\b(moe|mla)=None\b")
    found = []
    for path in sorted(C.BENCH.rglob("*.py")):
        rel = path.relative_to(C.BENCH).parts
        if rel[0] in ("families", "tests"):
            continue
        for i, line in enumerate(path.read_text().splitlines(), 1):
            if dense_words.search(line):
                found.append(f"{path.relative_to(C.ROOT)}:{i}: {line}")
    assert not found, "\n".join(found)
