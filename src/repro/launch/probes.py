"""Per-collective health probes + a hung-step watchdog.

PR 8's :class:`launch.telemetry.DriftMonitor` watches the *whole-step*
measured/predicted ratio; this module drops to per-collective-class
granularity — one tiny jitted probe program per ``comm_model``
collective class actually present on the mesh:

  * ``z_ring``   — the z-axis weight ring (``ring_all_gather``);
  * ``xy_ar``    — the activation all-reduce over the wider of x/y;
  * ``seq_ring`` — the context-parallel KV circulation
                   (``ring_exchange`` hops over the seq axis);
  * ``dp_rs_ag`` — the ZeRO data-axis round trip (reduce-scatter then
                   all-gather over the flattened data ring).

Each class carries two independent judgments:

  * a **DriftMonitor** against ``comm_model.collective_time`` priced by
    the ``--calib`` profile — the absolute calibrated verdict, merged
    into ``profile.probes`` as ``drift:collective:<class>`` via
    ``calibrate.merge_drift`` (see :meth:`CollectiveProbes.merge_into`);
  * a **rolling self-baseline** (median of this run's own probe times)
    — the relative verdict the :class:`Watchdog` uses to classify a
    stalled step as hung-collective vs slow-compute, meaningful even on
    an uncalibrated host where the absolute ratios are off by design.

The probe programs are separate jitted computations and never touch
``core.trace`` state, so the training step's HLO is byte-identical
whether probes run or not; with probes off nothing here is even built.

Fault injection: ``core.faultinject.FaultInjector.probe_delay`` sleeps
*inside* a probe's timed window, simulating a hung collective the same
way a sick link would surface — as that class's wall time, nothing
else's.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional

import jax
from jax import shard_map
import numpy as np

from repro.core import comm_model as CM
from repro.core import mesh as M
from repro.launch.telemetry import DriftMonitor

PROBE_CLASSES = ("z_ring", "xy_ar", "seq_ring", "dp_rs_ag")


@dataclasses.dataclass(frozen=True)
class ProbeResult:
    """One probe firing: absolute (vs the α-β model) and relative (vs
    this run's own history) views of a collective class's health."""

    cls: str
    kind: str            # comm_model collective kind
    p: int               # ring size
    elems: int           # buffer elements (comm_model conventions)
    measured_s: float
    predicted_s: float
    ratio: float         # rolling measured/predicted (DriftMonitor)
    jump: float          # measured / rolling self-baseline median
    injected_s: float    # simulated stall included in measured_s


def _axis_p(axes: M.MeshAxes, logical: str) -> int:
    return {"data": axes.dp, "x": axes.gx, "y": axes.gy, "z": axes.gz,
            "seq": axes.gseq}[logical]


class CollectiveProbes:
    """Builds and times one probe program per collective class present
    on ``(mesh, axes)``; classes whose ring size is 1 are skipped."""

    def __init__(self, mesh, axes: M.MeshAxes, hw: CM.HardwareParams = None,
                 *, elems: int = 1 << 14, window: int = 16,
                 band: float = 1.0, min_steps: int = 2, injector=None):
        from jax.sharding import PartitionSpec as P
        self.axes = axes
        self.hw = hw if hw is not None else CM.TPU_V5E
        self.injector = injector
        self._fns: Dict[str, Callable] = {}
        self._bufs: Dict[str, np.ndarray] = {}
        self.meta: Dict[str, dict] = {}      # cls -> kind/p/elems
        self.monitors: Dict[str, DriftMonitor] = {}
        self._hist: Dict[str, collections.deque] = {}
        self._warm = False

        def wrap(body, in_spec, out_spec):
            return jax.jit(shard_map(body, mesh=mesh, in_specs=(in_spec,),
                                     out_specs=out_spec, check_vma=False))

        def add(cls, kind, axis, p, fn, in_spec, out_spec, n, pred):
            if p <= 1 or pred <= 0:
                return
            self._fns[cls] = wrap(fn, in_spec, out_spec)
            self._bufs[cls] = np.arange(n, dtype=np.float32)
            self.meta[cls] = dict(kind=kind, p=p, elems=n)
            self.monitors[cls] = DriftMonitor(pred, window=window,
                                              band=band,
                                              min_steps=min_steps)
            self._hist[cls] = collections.deque(maxlen=window)

        # z ring: the weight-gather class (paper §3.2)
        p = _axis_p(axes, "z")
        if p > 1:
            n = -(-elems // p) * p
            add("z_ring", "all_gather", axes.z, p,
                lambda v: M.ring_all_gather(v, axes.z, dim=0),
                P(axes.z), P(None), n,
                CM.collective_time("all_gather", p, n, self.hw))
        # x/y all-reduce: the activation-reduction class; probe the
        # wider of the two rings (the one that dominates the model)
        ax = "x" if _axis_p(axes, "x") >= _axis_p(axes, "y") else "y"
        p = _axis_p(axes, ax)
        if p > 1:
            axis = axes.axis(ax)
            add("xy_ar", "all_reduce", axis, p,
                lambda v: M.ring_all_reduce(v, axis, dim=0),
                P(None), P(None), elems,
                CM.collective_time("all_reduce", p, elems, self.hw))
        # seq KV ring: each rank's block circulates all p-1 hops
        p = _axis_p(axes, "seq")
        if p > 1:
            axis = axes.seq
            block = -(-elems // p)

            def seq_ring(v, _axis=axis, _p=p):
                cur, acc = v, v
                for _ in range(_p - 1):
                    cur = M.ppermute_ring(cur, _axis)
                    acc = acc + cur
                return acc
            add("seq_ring", "ring_exchange", axis, p, seq_ring,
                P(axis), P(axis), block * p,
                CM.collective_time("ring_exchange", p, block, self.hw))
        # DP reduce-scatter + all-gather: the ZeRO round trip over the
        # flattened data ring
        p = axes.dp
        if p > 1:
            axis = axes.data
            n = -(-elems // p) * p

            def rs_ag(v, _axis=axis):
                s = M.ring_reduce_scatter(v, _axis, dim=0)
                return M.ring_all_gather(s, _axis, dim=0)
            add("dp_rs_ag", "reduce_scatter", axis, p, rs_ag,
                P(None), P(None), n,
                CM.collective_time("reduce_scatter", p, n, self.hw)
                + CM.collective_time("all_gather", p, n, self.hw))

    @property
    def classes(self) -> List[str]:
        return list(self._fns)

    def warmup(self) -> None:
        """Compile every probe (excluded from the monitors/baselines)."""
        for cls, fn in self._fns.items():
            jax.block_until_ready(fn(self._bufs[cls]))
        self._warm = True

    def run(self, step: int = 0) -> Dict[str, ProbeResult]:
        """Time every probe once; feeds the monitors and baselines."""
        if not self._warm:
            self.warmup()
        out: Dict[str, ProbeResult] = {}
        for cls, fn in self._fns.items():
            delay = (self.injector.probe_delay(step, cls)
                     if self.injector is not None else 0.0)
            t0 = time.perf_counter()
            res = fn(self._bufs[cls])
            if delay > 0:
                time.sleep(delay)  # the simulated hung collective
            jax.block_until_ready(res)
            measured = time.perf_counter() - t0
            mon = self.monitors[cls]
            ratio = mon.update(measured)
            hist = self._hist[cls]
            base = float(np.median(list(hist))) if hist else measured
            hist.append(measured)
            out[cls] = ProbeResult(
                cls=cls, measured_s=measured, ratio=ratio,
                predicted_s=mon.predicted_s,
                jump=measured / max(base, 1e-12), injected_s=delay,
                **self.meta[cls])
        return out

    def records(self) -> List[dict]:
        """Per-class drift payloads for ``calibrate.merge_drift``, keyed
        ``collective:<class>``."""
        return [mon.record(workload=f"collective:{cls}")
                for cls, mon in self.monitors.items() if mon.n]

    def merge_into(self, profile):
        """Fold every class's verdict into ``profile.probes``
        (``drift:collective:<class>`` keys)."""
        from repro.core import calibrate as CB
        return CB.merge_probes(profile, self.records())


class Watchdog:
    """Classifies a stalled training step: hung collective or just slow
    compute?

    ``observe`` feeds warm step times; a step is *stalled* when it
    exceeds ``factor`` x the rolling median. ``classify`` then fires
    every collective probe and blames the classes whose own time jumped
    by ``factor`` over their self-baseline — a hung collective stalls
    its class's probe the same way it stalls the step, while slow
    compute (thermal throttling, a noisy neighbor on the host) leaves
    the tiny probe programs untouched.
    """

    def __init__(self, probes: Optional[CollectiveProbes] = None, *,
                 factor: float = 3.0, window: int = 32,
                 min_steps: int = 3):
        self.probes = probes
        self.factor = float(factor)
        self.min_steps = int(min_steps)
        self.times: collections.deque = collections.deque(maxlen=window)

    def observe(self, step_s: float) -> None:
        self.times.append(float(step_s))

    @property
    def baseline_s(self) -> float:
        if not self.times:
            return float("nan")
        return float(np.median(list(self.times)))

    def stalled(self, step_s: float) -> bool:
        if len(self.times) < self.min_steps:
            return False
        return float(step_s) > self.factor * self.baseline_s

    def classify(self, step: int = 0) -> dict:
        """Verdict for a stalled step. Returns ``{"verdict":
        "hung_collective"|"slow_compute", "suspects": [cls...],
        "results": {cls: ProbeResult}}``."""
        if self.probes is None:
            return {"verdict": "slow_compute", "suspects": [],
                    "results": {}}
        results = self.probes.run(step)
        suspects = [cls for cls, r in results.items()
                    if r.jump > self.factor]
        return {"verdict": ("hung_collective" if suspects
                            else "slow_compute"),
                "suspects": suspects, "results": results}
