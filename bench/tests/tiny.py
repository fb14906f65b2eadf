"""Tiny cells for the CPU tests: the cells' own drivers and mesh shapes,
with the families' own CPU-size configurations."""
from __future__ import annotations

from bench import common as C


def variants():
    """(family, variant, configuration) for each ``tiny_configs()``
    entry of each family file."""
    return [(p.stem, name, cfg)
            for p in sorted(C.FAMILIES.glob("*.py"))
            for name, cfg in C.load_family(p.stem).tiny_configs().items()]


def config(variant: str) -> dict:
    """The tiny configuration of that name, of whichever family."""
    return next(dict(cfg) for _, name, cfg in variants() if name == variant)


def cell(name: str, cfg: dict, limits: dict, chips: int = 1):
    """A tiny copy of cell ``name``: its driver, mesh and metrics, with
    small traffic and the given limits."""
    bj = C.load_json(C.ROOT / "BENCHMARK.json")
    spec = dict(C.load_json(C.BENCH / "workloads" / f"{name}.json"))
    spec["limits"] = limits
    traffic = {"kind": "train", "global_batch": 4 * chips, "seq_len": 32,
               "markov_states": 64, "feed_batches": 4}
    return C.Cell(name=name, chips=chips, config_name="tiny", config=cfg,
                  traffic_name="tiny", traffic=traffic, spec=spec,
                  end_to_end=[m for m in bj["end_to_end"]
                              if C._applies(m, name)],
                  per_layer=[m for m in bj["per_layer"]
                             if C._applies(m, name)])
