"""Runtime telemetry: per-step metrics, MFU, and predicted-vs-measured
drift monitoring (docs/telemetry.md).

One :class:`Telemetry` recorder is shared by ``train.py``, ``serve.py``
and ``benchmarks/serving.py``. It appends schema'd JSONL records to
``runs/telemetry/<run>.jsonl`` (``--log-file`` overrides the path) and
prints a human summary table at exit. Record kinds:

  * ``meta``        — run header (arch, mesh, device count, the MFU
                      denominator constants, the predicted step time);
  * ``train_step``  — wall time (warmup-excluded), EMA, tokens/s, MFU,
                      loss/grad-norm, peak device bytes, drift ratio;
  * ``serve_step``  — one engine iteration: step kind (mixed/decode),
                      new tokens, queue depth, active slots, page-pool
                      utilization, cumulative preemptions;
  * ``drift``       — the rolling predicted-vs-measured verdict
                      (:meth:`DriftMonitor.record`) —
                      ``core.calibrate.merge_drift`` folds it back into
                      the calibration profile;
  * ``probe``       — one per-collective-class health probe firing
                      (``launch.probes``): measured vs α-β-predicted
                      time plus the jump over the class's own rolling
                      baseline;
  * ``event``       — a lifecycle/chaos event (rank loss, re-shard,
                      checkpoint corruption detected, watchdog verdict,
                      graceful shutdown) — the recovery audit trail;
  * ``summary``     — aggregates (p50/p99 step time, tokens/s, MFU,
                      peak bytes) written once at :meth:`Telemetry.close`.

**MFU** is ``model_flops_per_token(cfg) * tokens/s`` over the mesh's
aggregate peak FLOP/s — *model* flops (``6 * N_active`` per trained
token), not HLO flops, so remat recompute does not inflate it; the peak
is the device's published per-chip peak (:data:`DEVICE_PEAKS`). On the
CPU there is no such peak and MFU is null. Step timing blocks on the
step's metrics each iteration, so enabling telemetry serializes the
host loop with the device — a per-step cost the async default never
pays; the degenerate path (no ``--telemetry``) is unchanged.

**Drift** is the rolling median of measured/predicted step time, priced
by the ``--calib`` profile's ``comm_model.predict_step_time``. A ratio
drifting out of band means the analytic model no longer describes this
machine (new kernel mix, thermal throttling, a sick link) — the
ROADMAP's "collective health probes feeding the calibration profile"
direction starts here.
"""
from __future__ import annotations

import collections
import dataclasses
import json
import os
import time
from typing import Dict, List, Optional

import numpy as np

SCHEMA_VERSION = 1
DEFAULT_DIR = os.path.join("runs", "telemetry")

#: required numeric fields per record kind (beyond the envelope
#: ``v``/``run``/``kind`` every record carries). Nullable fields —
#: present but possibly None — are listed separately.
SCHEMA: Dict[str, tuple] = {
    "meta": (),
    "train_step": ("step", "step_s", "ema_s", "tok_s"),
    "serve_step": ("step", "step_s", "new_tokens", "queue_depth",
                   "active", "page_util", "preemptions"),
    "drift": ("predicted_s", "measured_p50_s", "ratio", "n"),
    "probe": ("step", "measured_s", "predicted_s", "ratio", "jump"),
    "event": ("step",),
    "summary": ("steps", "wall_s"),
}
NULLABLE: Dict[str, tuple] = {
    "train_step": ("mfu", "loss", "grad_norm", "peak_bytes", "drift"),
    "probe": ("injected_s",),
}


def validate_record(rec: dict) -> None:
    """Raise ValueError unless ``rec`` is a valid telemetry record."""
    for key in ("v", "run", "kind"):
        if key not in rec:
            raise ValueError(f"record missing envelope field {key!r}: {rec}")
    if rec["v"] != SCHEMA_VERSION:
        raise ValueError(f"unknown schema version {rec['v']!r}")
    kind = rec["kind"]
    if kind not in SCHEMA:
        raise ValueError(f"unknown record kind {kind!r}")
    for field in SCHEMA[kind]:
        if field not in rec:
            raise ValueError(f"{kind} record missing {field!r}: {rec}")
        if not isinstance(rec[field], (int, float)):
            raise ValueError(
                f"{kind}.{field} must be numeric, got {rec[field]!r}")
    for field in NULLABLE.get(kind, ()):
        if field in rec and rec[field] is not None \
                and not isinstance(rec[field], (int, float)):
            raise ValueError(
                f"{kind}.{field} must be numeric or null, got "
                f"{rec[field]!r}")


def validate_file(path: str) -> int:
    """Validate every line of a telemetry JSONL file; returns the record
    count (CI asserts on this)."""
    n = 0
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            validate_record(json.loads(line))
            n += 1
    if n == 0:
        raise ValueError(f"{path}: no telemetry records")
    return n


@dataclasses.dataclass(frozen=True)
class DevicePeak:
    """Published peak rates of one chip."""
    flops: float   # dense bf16 FLOP/s
    hbm_bw: float  # HBM bytes/s


#: Per-chip peaks keyed by ``jax.Device.device_kind``. Source: Google
#: Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 819 GB/s HBM).
DEVICE_PEAKS: Dict[str, DevicePeak] = {
    "TPU v5 lite": DevicePeak(flops=197e12, hbm_bw=819e9),
}


def device_peak(device=None) -> Optional[DevicePeak]:
    """The published peak of ``device`` (default: the first JAX device).

    None on the CPU, which has no published peak, so no utilization is
    reported there. An accelerator missing from :data:`DEVICE_PEAKS` is an
    error: a utilization priced against another chip's peak is wrong."""
    import jax
    d = device if device is not None else jax.devices()[0]
    if d.platform == "cpu":
        return None
    if d.device_kind not in DEVICE_PEAKS:
        raise ValueError(f"no published peak for device kind "
                         f"{d.device_kind!r} ({d.platform}); add it to "
                         f"launch.telemetry.DEVICE_PEAKS")
    return DEVICE_PEAKS[d.device_kind]


def peak_flops_per_device() -> Optional[float]:
    """MFU denominator per device: the table's bf16 peak, None on CPU."""
    peak = device_peak()
    return peak.flops if peak is not None else None


def peak_memory_bytes() -> Optional[int]:
    """Max ``peak_bytes_in_use`` over local devices, or None when the
    backend keeps no memory stats (host CPU does not)."""
    import jax
    best = None
    for d in jax.local_devices():
        v = (d.memory_stats() or {}).get("peak_bytes_in_use")
        if v is not None:
            best = v if best is None else max(best, v)
    return best


class DriftMonitor:
    """Rolling measured/predicted step-time ratio with an out-of-band
    warning (docs/telemetry.md §Drift).

    ``ratio`` is the rolling median over the last ``window`` steps —
    median, not mean, so one GC pause or checkpoint write cannot trip
    the alarm. Out of band means outside ``[1/(1+band), 1+band]`` after
    ``min_steps`` samples; :meth:`check` returns the warning message
    exactly once per excursion."""

    def __init__(self, predicted_s: float, *, window: int = 32,
                 band: float = 0.5, min_steps: int = 5):
        if predicted_s <= 0:
            raise ValueError(f"predicted_s must be > 0, got {predicted_s}")
        self.predicted_s = float(predicted_s)
        self.band = float(band)
        self.min_steps = int(min_steps)
        self.ratios: collections.deque = collections.deque(maxlen=window)
        self.n = 0
        self.warned = False

    def update(self, measured_s: float) -> float:
        """Record one measured step; returns the rolling ratio."""
        self.ratios.append(float(measured_s) / self.predicted_s)
        self.n += 1
        return self.ratio

    @property
    def ratio(self) -> float:
        if not self.ratios:
            return float("nan")
        return float(np.median(list(self.ratios)))

    @property
    def out_of_band(self) -> bool:
        if self.n < self.min_steps:
            return False
        r = self.ratio
        return r > 1.0 + self.band or r < 1.0 / (1.0 + self.band)

    def check(self) -> Optional[str]:
        """Warning message when newly out of band, else None."""
        if not self.out_of_band:
            self.warned = False
            return None
        if self.warned:
            return None
        self.warned = True
        return (f"drift: measured/predicted step time "
                f"{self.ratio:.2f}x is outside the "
                f"[{1.0 / (1.0 + self.band):.2f}, "
                f"{1.0 + self.band:.2f}] band "
                f"(predicted {self.predicted_s * 1e3:.2f} ms) — "
                f"recalibrate (python -m benchmarks.calibrate) or merge "
                f"this run's drift record (core.calibrate.merge_drift)")

    def record(self, *, workload: str = "step") -> dict:
        """The drift payload ``core.calibrate.merge_drift`` consumes."""
        return {
            "workload": workload,
            "predicted_s": self.predicted_s,
            "measured_p50_s": self.ratio * self.predicted_s,
            "ratio": self.ratio,
            "n": self.n,
            "band": self.band,
            "out_of_band": self.out_of_band,
        }


@dataclasses.dataclass
class _StepStats:
    """Warmup-excluded accumulators over one run."""
    times: List[float] = dataclasses.field(default_factory=list)
    ema_s: Optional[float] = None
    tokens: int = 0

    def push(self, step_s: float, tokens: int, alpha: float) -> float:
        self.times.append(step_s)
        self.tokens += tokens
        self.ema_s = (step_s if self.ema_s is None
                      else alpha * step_s + (1.0 - alpha) * self.ema_s)
        return self.ema_s

    def percentile(self, q: float) -> float:
        if not self.times:
            return float("nan")
        return float(np.percentile(self.times, q))


class Telemetry:
    """JSONL telemetry sink + aggregator (one instance per run).

    ``flops_per_token`` / ``peak_flops_per_device`` / ``n_devices``
    parameterize MFU (a zero or None disables it); ``tokens_per_step``
    is the training global batch in tokens; ``drift`` is an optional
    :class:`DriftMonitor` priced from the ``--calib`` profile."""

    def __init__(self, run: str, *, path: Optional[str] = None,
                 out_dir: str = DEFAULT_DIR, tokens_per_step: int = 0,
                 flops_per_token: float = 0.0,
                 peak_flops_per_device: Optional[float] = None,
                 n_devices: int = 1,
                 drift: Optional[DriftMonitor] = None, ema: float = 0.1,
                 meta: Optional[dict] = None, verbose: bool = True):
        self.run = run
        self.path = path or os.path.join(out_dir, f"{run}.jsonl")
        self.tokens_per_step = int(tokens_per_step)
        self.flops_per_token = float(flops_per_token)
        self.peak_flops = (float(peak_flops_per_device) * int(n_devices)
                           if peak_flops_per_device else None)
        self.drift = drift
        self.ema_alpha = float(ema)
        self.verbose = verbose
        self.stats = _StepStats()
        self.serve_tokens = 0
        self.serve_steps = 0
        self._t0 = time.time()
        self._closed = False
        os.makedirs(os.path.dirname(self.path) or ".", exist_ok=True)
        self._f = open(self.path, "w")
        head = {"tokens_per_step": self.tokens_per_step,
                "flops_per_token": self.flops_per_token,
                "peak_flops": self.peak_flops,
                "predicted_step_s": (drift.predicted_s if drift else None),
                "t0_unix": self._t0}
        head.update(meta or {})
        self._emit("meta", head)

    # ------------------------------------------------------------------ #
    def _emit(self, kind: str, fields: dict) -> dict:
        rec = {"v": SCHEMA_VERSION, "run": self.run, "kind": kind}
        rec.update(fields)
        validate_record(rec)
        self._f.write(json.dumps(rec) + "\n")
        self._f.flush()
        return rec

    def mfu(self, tok_s: float) -> Optional[float]:
        if self.flops_per_token <= 0 or not self.peak_flops:
            return None
        return self.flops_per_token * tok_s / self.peak_flops

    # ------------------------------------------------------------------ #
    def train_step(self, step: int, step_s: float, *,
                   loss: Optional[float] = None,
                   grad_norm: Optional[float] = None) -> dict:
        """Record one warm optimizer step (callers exclude step 0: its
        wall time is compile, not steady state)."""
        ema = self.stats.push(step_s, self.tokens_per_step, self.ema_alpha)
        tok_s = self.tokens_per_step / max(step_s, 1e-12)
        ratio = None
        if self.drift is not None:
            self.drift.update(step_s)
            ratio = self.drift.ratio
            msg = self.drift.check()
            if msg and self.verbose:
                print(f"WARNING [{self.run}] {msg}", flush=True)
        return self._emit("train_step", {
            "step": step, "step_s": step_s, "ema_s": ema, "tok_s": tok_s,
            "mfu": self.mfu(tok_s), "loss": loss, "grad_norm": grad_norm,
            "peak_bytes": peak_memory_bytes(), "drift": ratio})

    def probe(self, step: int, result) -> dict:
        """Record one collective-probe firing (``launch.probes
        .ProbeResult``)."""
        return self._emit("probe", {
            "step": int(step), "cls": result.cls,
            "collective": result.kind,
            "p": int(result.p), "elems": int(result.elems),
            "measured_s": float(result.measured_s),
            "predicted_s": float(result.predicted_s),
            "ratio": float(result.ratio), "jump": float(result.jump),
            "injected_s": (float(result.injected_s)
                           if result.injected_s else None)})

    def event(self, step: int, event: str, **fields) -> dict:
        """Record a lifecycle/chaos event (free-form string/number
        fields beyond the required ``step``) — the recovery audit
        trail chaos tests and operators read back."""
        return self._emit("event", dict({"step": int(step),
                                         "event": str(event)}, **fields))

    def serve_step(self, step: int, step_s: float, *, new_tokens: int,
                   queue_depth: int, active: int, page_util: float,
                   preemptions: int, step_kind: str = "decode") -> dict:
        """Record one engine iteration (``preemptions`` cumulative)."""
        self.stats.push(step_s, new_tokens, self.ema_alpha)
        self.serve_tokens += int(new_tokens)
        self.serve_steps += 1
        return self._emit("serve_step", {
            "step": step, "step_s": step_s, "step_kind": step_kind,
            "new_tokens": int(new_tokens), "queue_depth": int(queue_depth),
            "active": int(active), "page_util": float(page_util),
            "preemptions": int(preemptions)})

    # ------------------------------------------------------------------ #
    def close(self, extra: Optional[dict] = None) -> dict:
        """Write the drift + summary records, print the human table, and
        close the file. ``extra`` fields override the computed summary
        (the serving callers pass the engine's own tokens/s so the JSONL
        and runs/perf/serving.csv agree by construction)."""
        if self._closed:
            return {}
        self._closed = True
        wall = time.time() - self._t0
        n = len(self.stats.times)
        p50, p99 = self.stats.percentile(50), self.stats.percentile(99)
        tok_s = (self.stats.tokens / sum(self.stats.times)
                 if self.stats.times and sum(self.stats.times) > 0 else None)
        summary = {
            "steps": n, "wall_s": wall, "step_p50_s": p50,
            "step_p99_s": p99, "ema_s": self.stats.ema_s,
            "tok_s": tok_s, "mfu": self.mfu(tok_s) if tok_s else None,
            "peak_bytes": peak_memory_bytes(),
        }
        drift_rec = None
        if self.drift is not None and self.drift.n:
            drift_rec = self.drift.record()
            self._emit("drift", drift_rec)
            summary["drift"] = drift_rec["ratio"]
        summary.update(extra or {})
        rec = self._emit("summary", summary)
        self._f.close()
        if self.verbose:
            self._print_table(summary)
        return rec

    def _print_table(self, s: dict) -> None:
        def fmt(k, v):
            if v is None:
                return "-"
            if k == "tok_s":
                return f"{v:,.0f}"
            if k.endswith("_s") and k != "steps":
                return f"{v * 1e3:,.2f} ms"
            if k == "mfu":
                return f"{v * 100:.2f}%"
            if k == "peak_bytes":
                return f"{v / 2**20:,.1f} MiB"
            if isinstance(v, float):
                return f"{v:,.3f}"
            return str(v)
        print(f"telemetry [{self.run}] -> {self.path}")
        for k in ("steps", "step_p50_s", "step_p99_s", "ema_s", "tok_s",
                  "mfu", "peak_bytes", "drift"):
            if k in s:
                print(f"  {k:<12} {fmt(k, s[k])}")
