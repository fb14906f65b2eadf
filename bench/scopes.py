"""Which layer of the model each instruction of the compiled step belongs
to, read from the ``op_name`` metadata of its optimized HLO
(``Compiled.as_text()``), and the device time of each layer in a traced
window.

The program names its layers with ``jax.named_scope`` (the names are
``repro.core.trace.LAYERS``), so an op's ``op_name`` reads like
``jit(step)/transpose(jvp())/while/body/…/attn/attn_core/dot_general``.
An instruction's layer is the innermost component of its ``op_name`` that
is a layer name, once transform wrappers (``jvp(…)``, ``transpose(…)``)
are taken off; where the name joins several with ``;``, the first counts.
A fusion whose own name has no layer (its root is loop plumbing, such as
the ``dynamic-update-slice`` that writes a scanned layer's gradient) takes
the layer of the instructions it fuses, root first.

The readers get the map from :func:`run_map`: the step of the cell this
process ran (``--workload`` on its command line), compiled once more
after the window. Set-up wrote that step to the persistent compile
cache, so this compile loads the executable the window ran, with the
same instruction names. A build without layer scopes (the program before
they were added, or an executable compiled without them) gives no map:
the readers then read nothing rather than 100 % unscoped, and nothing is
compiled for a program without layer names.
"""
from __future__ import annotations

import argparse
import functools
import re
import sys
import time
from typing import Collection, Dict, Iterable, List, Optional, Tuple

from bench import trace_reduce as TRR

#: The key of compute ops that carry no layer name.
UNSCOPED = "unscoped"
#: Instruction name -> (opcode, layer or None).
ScopeMap = Dict[str, Tuple[str, Optional[str]]]

_COMP = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$")
_INSTR = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (.*?) ([\w\-]+)\(")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_WRAP = re.compile(r"^(?:jvp|transpose|vmap|linearize|remat|checkpoint)"
                   r"\((.*)\)$")


def program_layers() -> Optional[Tuple[str, ...]]:
    """The program's layer names, or None where it has none."""
    try:
        from repro.core.trace import LAYERS
    except ImportError:
        return None
    return LAYERS


def layer_of(op_name: str, layers: Collection[str]) -> Optional[str]:
    """The innermost layer name in ``op_name``, or None."""
    first = op_name.split(";")[0]
    for part in reversed(first.split("/")):
        while (m := _WRAP.match(part)):
            part = m.group(1)
        if part in layers:
            return part
    return None


def computations(hlo_text: str) -> Dict[str, List[Tuple[str, str, str]]]:
    """An HLO module's text -> each computation's instructions in order,
    as (name, opcode, line); a computation's root is its last."""
    comps: Dict[str, List[Tuple[str, str, str]]] = {}
    body = None
    for line in hlo_text.splitlines():
        if body is None:
            m = _COMP.match(line)
            if m:
                body = comps.setdefault(m.group(1), [])
        elif line.startswith("}"):
            body = None
        else:
            m = _INSTR.match(line)
            if m:
                body.append((m.group(1), m.group(3), line))
    return comps


def instruction_layers(hlo_text: str, layers: Iterable[str]) -> ScopeMap:
    """Every instruction of an HLO module's text -> (opcode, layer)."""
    layers = frozenset(layers)
    comps = computations(hlo_text)
    out: ScopeMap = {}
    fused: Dict[str, str] = {}
    for body in comps.values():
        for name, opcode, line in body:
            on = _OP_NAME.search(line)
            out[name] = (opcode,
                         layer_of(on.group(1), layers) if on else None)
            c = _CALLS.search(line) if opcode == "fusion" else None
            if c:
                fused[name] = c.group(1)
    for name, comp in fused.items():
        if out[name][1] is None:
            inner = (out[n][1] for n, _, _ in reversed(comps.get(comp, [])))
            out[name] = (out[name][0], next((l for l in inner if l), None))
    return out


def scope_map(hlo_text: str, layers: Iterable[str]
              ) -> Optional[ScopeMap]:
    """:func:`instruction_layers`; None, said on standard error, where
    no instruction carries a layer name."""
    out = instruction_layers(hlo_text, layers)
    if not any(layer for _, layer in out.values()):
        print("scopes: no instruction of the executable carries a layer "
              "name in its op_name; no device_share.* reading",
              file=sys.stderr, flush=True)
        return None
    return out


@functools.lru_cache(maxsize=None)
def run_map() -> Optional[ScopeMap]:
    """:func:`scope_map` of the train step of the cell this process runs
    (``--workload`` of ``bench/run.py``'s command line), compiled again:
    a hit in the persistent compile cache that set-up filled. None,
    said on standard error and without compiling, for a program without
    layer names. The times taken go to standard error."""
    layers = program_layers()
    if layers is None:
        print("scopes: the program names no layers "
              "(repro.core.trace.LAYERS); no device_share.* reading",
              file=sys.stderr, flush=True)
        return None
    from bench import common as C
    from bench.drivers import train as DT
    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--workload", required=True)
    cell = C.load_cell(ap.parse_known_args()[0].workload)
    t0 = time.perf_counter()
    step = DT.Trainer(cell, C.require_devices(cell.chips)).step
    t1 = time.perf_counter()
    text = step.as_text()
    t2 = time.perf_counter()
    out = scope_map(text, layers)
    print(f"scopes: step compiled again {t1 - t0:.3f} s, as_text "
          f"{t2 - t1:.3f} s ({len(text)} bytes), map "
          f"{time.perf_counter() - t2:.3f} s", file=sys.stderr, flush=True)
    return out


def is_collective(name: str, opcode: str) -> bool:
    """The test ``trace_reduce.collective_kind`` applies to an op event,
    on the instruction's name and opcode."""
    return TRR.collective_kind(f"{name} {opcode}") is not None


def _layer_key(label: str, smap: ScopeMap) -> Optional[str]:
    """An op label's layer, :data:`UNSCOPED` for a compute op without
    one (or not in the map), None for a collective."""
    name = label.split(" ", 1)[0]
    opcode, layer = smap.get(name, ("", None))
    if is_collective(name, opcode):
        return None
    return layer or UNSCOPED


def layer_seconds(ops: Dict[str, float], smap: ScopeMap
                  ) -> Dict[str, float]:
    """Seconds of compute per layer from ``trace_reduce.reduce``'s
    ``ops`` (label -> seconds, control ops already left out; a label
    starts with the instruction's name). Collectives are left out; an
    op with no layer, or not in the map, counts as :data:`UNSCOPED`."""
    out: Dict[str, float] = {}
    for label, t in ops.items():
        key = _layer_key(label, smap)
        if key is not None:
            out[key] = out.get(key, 0.0) + t
    return out


def log_unscoped(ctx: dict, n: int = 8) -> None:
    """The ``n`` longest compute ops without a layer, on standard
    error: what the coverage guard counts."""
    t = ctx.get("trace")
    smap = run_map() if t else None
    if not smap:
        return
    ops = sorted(((s, label) for label, s in t["ops"].items()
                  if _layer_key(label, smap) == UNSCOPED), reverse=True)
    for s, label in ops[:n]:
        print(f"scopes: unscoped {label}: {s:.6f} s", file=sys.stderr,
              flush=True)


def share(ctx: dict, layer: str) -> Optional[float]:
    """Device time of ``layer``'s compute ops over the traced window,
    mean over the chips, in percent; None without a trace or a map."""
    t = ctx.get("trace")
    smap = run_map() if t else None
    if not smap:
        return None
    return 100.0 * layer_seconds(t["ops"], smap).get(layer, 0.0) \
        / t["window_s"]
