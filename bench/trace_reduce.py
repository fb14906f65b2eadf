"""From a profiler trace to numbers: device busy time, time per op,
collective time and the part of it that no other op hides, and idle gaps
named by the host span open at the time.

The trace is read with ``jax.profiler.ProfileData`` (no other library).
Device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one
event per executed HLO op. Host spans are the ``TraceAnnotation`` events
the benchmark writes (``bench.common.Spans``), on the same clock.
"""
from __future__ import annotations

import glob
import os
import re
import shutil
from typing import Dict, Iterable, List, Optional, Tuple

Interval = Tuple[float, float]

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def total(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """Parts of the union ``a`` not covered by the union ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


_HLO = re.compile(r"^%?([\w.\-]+) = (.*?) ([\w\-]+)\(")
#: Ops whose events span the ops of their bodies, which have their own.
_CONTROL = ("while", "conditional", "call")


def op_label(text: str) -> Tuple[str, bool]:
    """An op event's HLO text -> (``name output-shape``, is control flow).
    The trace names an op by its whole instruction text."""
    m = _HLO.match(text)
    if not m:
        return text[:100], False
    shape = re.sub(r"\{[^}]*\}", "", m.group(2))
    return f"{m.group(1)} {shape[:80]}", m.group(3) in _CONTROL


def collective_kind(text: str) -> Optional[str]:
    """The collective an op event is (by its name and opcode, not by its
    operands), or None."""
    m = _HLO.match(text)
    key = (m.group(1) + " " + m.group(3) if m else text).lower()
    for k in COLLECTIVES:
        if k in key:
            return k
    return None


def device_ops(events: List[Tuple[str, float, float]]):
    """One chip's op events -> (compute, collective) interval unions."""
    compute, coll = [], []
    for name, s, e in events:
        (compute if collective_kind(name) is None else coll).append((s, e))
    return union(compute), union(coll)


def load(trace_dir: str) -> dict:
    """Read the newest ``.xplane.pb`` under ``trace_dir`` into plain
    lists: per device its op events, and the host's annotation events."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise RuntimeError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(files[-1])
    devices: Dict[int, list] = {}
    host: List[Tuple[str, float, float]] = []
    for plane in pd.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            evs = devices.setdefault(int(m.group(1)), [])
            for line in plane.lines:
                # XLA Ops: every op on the core; Async XLA Ops: an async
                # op from start to done, of which the collectives count
                if line.name not in ("XLA Ops", "Async XLA Ops"):
                    continue
                asyn = line.name == "Async XLA Ops"
                for ev in line.events:
                    if asyn and collective_kind(ev.name) is None:
                        continue
                    evs.append((ev.name, ev.start_ns * 1e-9,
                                (ev.start_ns + ev.duration_ns) * 1e-9))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    host.append((ev.name, ev.start_ns * 1e-9,
                                 (ev.start_ns + ev.duration_ns) * 1e-9))
    return {"devices": devices, "host": host}


def reduce(raw: dict, span_names: Iterable[str], window_name: str) -> dict:
    """Numbers of the traced window, means over the chips.

    Returns ``busy_s``, ``window_s``, ``collective_s``,
    ``collective_exposed_s``, ``ops`` (seconds per op kind), the chips'
    busy intervals, the named host spans, and ``gaps`` (idle gaps of the
    first chip, each named by the innermost host span covering it)."""
    names = set(span_names) | {window_name}
    spans = [(n, s, e) for n, s, e in raw["host"] if n in names]
    win = [(s, e) for n, s, e in spans if n == window_name]
    if not win or not raw["devices"]:
        return {}
    ws, we = win[0]
    busy, coll, exposed, ops, chips = [], [], [], {}, {}
    for dev, events in sorted(raw["devices"].items()):
        inside = [(n, max(s, ws), min(e, we)) for n, s, e in events
                  if e > ws and s < we]
        comp, col = device_ops(inside)
        allu = union(comp + col)
        chips[dev] = allu
        busy.append(total(allu))
        coll.append(total(col))
        exposed.append(total(subtract(col, comp)))
        for n, s, e in inside:
            label, control = op_label(n)
            if not control:
                ops[label] = ops.get(label, 0.0) + (e - s)
    k = len(chips)
    first = chips[min(chips)]
    gaps = []
    prev = ws
    for s, e in first + [(we, we)]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    named = []
    inner = [(n, s, e) for n, s, e in spans if n != window_name]
    for s, e in gaps:
        mid = 0.5 * (s + e)
        cover = [(e2 - s2, n) for n, s2, e2 in inner if s2 <= mid <= e2]
        named.append((min(cover)[1] if cover else "no span", e - s))
    named.sort(key=lambda x: -x[1])
    return {"window_s": we - ws, "busy_s": sum(busy) / k,
            "collective_s": sum(coll) / k,
            "collective_exposed_s": sum(exposed) / k,
            "ops": {n: t / k for n, t in ops.items()},
            "chips": chips, "spans": spans, "gaps": named}


def busy_within(chip_busy: List[Interval], lo: float, hi: float) -> float:
    return total(clip(chip_busy, lo, hi))


def breakdown(red: dict) -> dict:
    ops = sorted(red["ops"].items(), key=lambda x: -x[1])[:10]
    return {"device_ops": [[n, t] for n, t in ops],
            "idle_gaps": [[n, t] for n, t in red["gaps"][:10]]}


def discard(trace_dir: str) -> None:
    shutil.rmtree(trace_dir, ignore_errors=True)


class Capture:
    """A profiler trace around the window when ``on``; ``reduced`` holds
    the window's numbers once the block has closed."""

    def __init__(self, on: bool, spans, trace_dir):
        self.on, self.spans, self.dir = on, spans, str(trace_dir)
        self.reduced = None

    def __enter__(self):
        if self.on:
            import jax
            discard(self.dir)
            jax.profiler.start_trace(self.dir)
            self.spans.tracing = True
        return self

    def __exit__(self, *exc):
        if self.on:
            import jax
            jax.profiler.stop_trace()
            self.spans.tracing = False
        return False

    def read(self, window_name: str) -> dict:
        names = {n for n, _, _ in self.spans.records}
        self.reduced = reduce(load(self.dir), names, window_name)
        discard(self.dir)
        return self.reduced
