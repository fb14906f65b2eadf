"""Shared plumbing of the benchmark: paths, the cell's files, the chip
check, the compile cache, seeds, host spans and the result line.

Everything that belongs to one cell, configuration, traffic mix or
per-layer metric lives in a file of its own under ``bench/`` and is found
here by its name in ``BENCHMARK.json``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import sys
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
#: JAX's persistent compilation cache. A fixed path inside the checkout:
#: the path is part of the cache key, so a moving directory never hits.
CACHE_DIR = ROOT / ".bench_cache" / "jax"
#: Scratch for profiler traces (deleted after they are read).
RUN_DIR = ROOT / ".bench_cache" / "run"


def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    """One entry of ``BENCHMARK.json``'s workloads with its own files."""
    name: str
    chips: int
    config_name: str
    config: Dict[str, Any]       # bench/configs/<config>.json
    traffic_name: str
    traffic: Dict[str, Any]      # bench/traffic/<traffic>.json
    spec: Dict[str, Any]         # bench/workloads/<cell>.json
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench_json: Optional[Path] = None) -> Cell:
    bj = load_json(bench_json or ROOT / "BENCHMARK.json")
    entry = next((w for w in bj["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; known: "
                         f"{[w['name'] for w in bj['workloads']]}")
    cfg = next(c for c in bj["configs"] if c["name"] == entry["config"])
    spec = load_json(BENCH / "workloads" / f"{name}.json")
    return Cell(
        name=name, chips=int(entry["chips"]), config_name=cfg["name"],
        config=load_json(ROOT / cfg["file"]),
        traffic_name=entry["traffic"],
        traffic=load_json(BENCH / "traffic" / f"{entry['traffic']}.json"),
        spec=spec,
        end_to_end=[m for m in bj["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bj["per_layer"] if _applies(m, name)])


def require_devices(n: int) -> list:
    """The first ``n`` accelerator devices. Raises when JAX runs on the
    CPU or finds fewer than ``n``: nothing here falls back to the CPU."""
    import jax
    devices = jax.devices()
    if devices[0].platform == "cpu":
        raise SystemExit(f"the benchmark needs an accelerator; JAX found "
                         f"only {devices[0].platform}")
    if len(devices) < n:
        raise SystemExit(f"the cell needs {n} chips; JAX found "
                         f"{len(devices)}")
    return devices[:n]


def enable_compile_cache() -> None:
    import jax
    CACHE_DIR.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def base_key(seed: int):
    """A PRNG key for any whole number: ``PRNGKey`` alone keeps only the
    low 32 bits and maps larger seeds to key 0."""
    import jax
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


class Spans:
    """Host spans: kept in memory as (name, start, end) on the
    ``perf_counter`` clock and, while a profiler trace runs, also written
    into it as ``TraceAnnotation`` events so device gaps can be named."""

    def __init__(self):
        self.records: List[tuple] = []
        self.tracing = False

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0 = time.perf_counter()
        if self.tracing:
            import jax
            with jax.profiler.TraceAnnotation(name):
                yield
        else:
            yield
        self.records.append((name, t0, time.perf_counter()))


def device_info(devices) -> Dict[str, Any]:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def load_reader(metric: str):
    """The ``read`` function of ``bench/metrics/<metric>.py``."""
    path = BENCH / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def emit(result: Dict[str, Any], checks: Dict[str, Dict[str, float]]):
    """Print the compared numbers beside their limits as the last lines
    of standard error, then the result line (``checks`` last) as the last
    line of standard output."""
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    out = dict(result)
    out["checks"] = checks
    print(json.dumps(out), flush=True)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def stable_seed(*parts: int) -> int:
    """Mix whole numbers into one numpy seed (any size)."""
    h = 1469598103934665603
    for p in parts:
        h = ((h ^ (int(p) & 0xFFFFFFFFFFFFFFFF)) * 1099511628211) \
            & 0xFFFFFFFFFFFFFFFF
    return h
