"""Hardware calibration: measured α/β/overlap fits for the analytic model.

``comm_model.HardwareParams`` prices every collective as
``steps · α + wire_bytes / link_bw`` and every GEMM as ``flops_needed /
flops`` — but its defaults are *guessed* TPU-v5e constants. This module
closes the loop the ROADMAP kept deferring: time the real primitives on
the live backend, least-squares-fit the constants, and persist them as a
:class:`CalibrationProfile` that ``--calib <path|auto>`` loads back into
:class:`~repro.core.comm_model.HardwareParams` on the dryrun / train /
hillclimb / benchmark CLIs.

What is measured (``benchmarks/calibrate.py`` is the CLI harness):

  * **γ/α/β per axis class** — ring all-gather / reduce-scatter /
    all-reduce (``core.mesh`` ring helpers), the blocking ``psum`` and
    the ``ring_exchange`` KV circulation of ring attention (the seq
    axis' collective class: p−1 ppermute hops of a full per-rank block)
    over each mapped mesh axis AND the flattened tuple ring (two hop
    counts separate the constants), across a message-size sweep. Each
    timing is one sample ``t = γ + steps · α + wire_bytes · β`` with
    the hop counts and bandwidth-optimal wire bytes of
    ``comm_model.collective_time`` (AR = 2(p−1) hops, AG/RS = p−1; γ
    is the per-call launch overhead, LogGP's ``o`` — it dominates on
    CPU backends, α on ring interconnects); :func:`fit_constants`
    solves the stacked system by least squares, so on synthetic data
    generated from the model the fit recovers the constants exactly
    (tests/test_calibrate.py pins this).
  * **GEMM throughput** — achieved matmul FLOP/s over a size sweep
    (the ``flops`` constant; the best size wins, matching how the model
    prices a layer's well-shaped GEMMs).
  * **Overlap probe** — the same ring issued *under* an independent
    matmul vs back-to-back: the hidden fraction is the measured
    ``overlap_efficiency``. Probed separately for an all-gather ring
    (the z-axis weight pattern) and an all-reduce ring (the x/y
    activation pattern); comparing the two answers the z-rings-claim-
    first question (``z_claims_first`` — ``layer_time`` consults it).
  * **Cross-step probe** — a terminal all-gather followed by an
    independent "next-step" matmul, fused vs sequential: the hidden
    fraction calibrates ``cross_step_efficiency``, which scales the
    cross-step window of ``comm_model.dp_sync_time``.

Units: α in seconds per ring hop, γ in seconds per collective call, β
in seconds per wire byte (``link_bw = 1/β`` bytes/s), ``flops`` in
FLOP/s, efficiencies in [0, 1].
An *uncalibrated* run is bitwise unchanged: ``resolve_hw(None)`` returns
the ``TPU_V5E`` defaults and the new ``HardwareParams`` fields default to
the pre-calibration behaviour (``z_claims_first=True``,
``cross_step_efficiency=1.0``).

Profiles persist to ``runs/calib/<backend>.json`` (:meth:`Calibration
Profile.save`); ``resolve("auto")`` finds the live backend's file.
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core import comm_model as CM

DEFAULT_DIR = os.path.join("runs", "calib")

#: (kind -> (hop count, wire-byte factor)) as functions of ring size p and
#: the *full* buffer bytes, matching comm_model.collective_time's
#: conventions: all_reduce takes the reduced buffer, AG/RS the full one.
_KINDS = ("all_gather", "reduce_scatter", "all_reduce", "psum",
          "ring_exchange", "all_to_all")


def collective_geometry(kind: str, p: int, buf_bytes: float
                        ) -> Tuple[int, float]:
    """(ring hops, wire bytes) of one bandwidth-optimal collective —
    the regressor row of the α/β fit. ``psum`` is priced as the
    all-reduce it is (same wire bytes; the blocking lowering still pays
    per-hop latency on a ring topology). ``ring_exchange`` is the
    seq-axis KV circulation of ring attention (p-1 ppermute hops each
    forwarding the rank's 1/p block of ``buf_bytes``; note
    ``comm_model.collective_time`` takes the per-rank *block* for this
    kind while the harness here times the full buffer)."""
    if p <= 1:
        return 0, 0.0
    if kind in ("all_reduce", "psum"):
        return 2 * (p - 1), 2.0 * (p - 1) / p * buf_bytes
    if kind in ("all_gather", "reduce_scatter", "ring_exchange"):
        return p - 1, (p - 1) / p * buf_bytes
    if kind == "all_to_all":
        # MoE dispatch: every rank keeps its 1/p block and sends the
        # other (p-1)/p of the buffer, one pairwise exchange per hop
        return p - 1, (p - 1) / p * buf_bytes
    raise ValueError(f"unknown collective kind {kind!r}")


@dataclasses.dataclass(frozen=True)
class Sample:
    """One timed collective: the fit's (steps, wire_bytes) -> seconds row."""

    kind: str
    axis: str
    p: int
    elems: int          # buffer elements (comm_model conventions)
    steps: int
    wire_bytes: float
    seconds: float

    def as_dict(self):
        return dataclasses.asdict(self)


def fit_constants(samples: Sequence[Sample]
                  ) -> Tuple[float, float, float, float]:
    """Least-squares (γ, α, β, R²) over
    ``t = γ + steps·α + wire_bytes·β`` (one call per sample).

    γ is the per-collective launch overhead (LogGP's ``o`` — hop-count
    independent), α the per-ring-hop latency, β seconds per wire byte.
    Identifiable when the samples span at least two distinct hop counts
    (AG/RS vs AR at one ring size already do; mixing ring sizes — the
    tuple-axis sweep of :func:`run_calibration` — sharpens it). Exact on
    noiseless synthetic data; negative solutions are clamped to 0 by
    coordinate re-solve — a fit cannot claim negative latency or
    bandwidth time."""
    rows = [s for s in samples if s.steps > 0]
    if len(rows) < 3:
        raise ValueError("need >= 3 samples with p > 1 to fit "
                         "gamma/alpha/beta")
    A = np.array([[1.0, s.steps, s.wire_bytes] for s in rows],
                 dtype=np.float64)
    t = np.array([s.seconds for s in rows], dtype=np.float64)
    sol, *_ = np.linalg.lstsq(A, t, rcond=None)
    if np.any(sol < 0.0):
        # re-solve with the negative coordinates pinned to zero
        keep = [i for i in range(3) if sol[i] > 0.0] or [2]
        sub, *_ = np.linalg.lstsq(A[:, keep], t, rcond=None)
        sol = np.zeros(3)
        for i, v in zip(keep, sub):
            sol[i] = max(float(v), 0.0)
    gamma, alpha, beta = (float(sol[0]), float(sol[1]), float(sol[2]))
    pred = A @ np.array([gamma, alpha, beta])
    ss_res = float(np.sum((t - pred) ** 2))
    ss_tot = float(np.sum((t - np.mean(t)) ** 2))
    r2 = 1.0 - ss_res / ss_tot if ss_tot > 0 else 1.0
    return gamma, alpha, beta, r2


@dataclasses.dataclass(frozen=True)
class AxisFit:
    """Fitted γ/α/β for one mesh-axis class (or a flattened tuple)."""

    axis: str
    p: int
    alpha: float        # seconds per ring hop
    beta: float         # seconds per wire byte (1/bandwidth)
    r2: float
    n_samples: int
    gamma: float = 0.0  # seconds per collective call

    @property
    def link_bw(self) -> float:
        return 1.0 / self.beta if self.beta > 0 else float("inf")

    def as_dict(self):
        return dataclasses.asdict(self)


@dataclasses.dataclass(frozen=True)
class CalibrationProfile:
    """Measured hardware constants, persistable and loadable into
    :class:`~repro.core.comm_model.HardwareParams`.

    ``alpha``/``link_bw``/``flops``/``overlap_efficiency`` are the
    aggregate fits the analytic model consumes; ``axis_fits`` keeps the
    per-axis-class α/β so per-axis pricing stays available to readers of
    the JSON (EXPERIMENTS.md §Calibration tabulates them)."""

    backend: str
    n_devices: int
    mesh_shape: Tuple[int, ...]
    alpha: float
    link_bw: float
    flops: float
    overlap_efficiency: float
    gamma: float = 0.0
    z_claims_first: bool = True
    cross_step_efficiency: float = 1.0
    bytes_per_elem: float = 2.0
    fit_r2: float = 0.0
    axis_fits: Tuple[AxisFit, ...] = ()
    probes: Dict[str, float] = dataclasses.field(default_factory=dict)
    samples: Tuple[Sample, ...] = ()
    created: str = ""

    # ------------------------------------------------------------------ #
    def hardware_params(self) -> CM.HardwareParams:
        """The fitted constants in the analytic model's terms."""
        return CM.HardwareParams(
            alpha=self.alpha, gamma=self.gamma, link_bw=self.link_bw,
            flops=self.flops, bytes_per_elem=self.bytes_per_elem,
            overlap_efficiency=self.overlap_efficiency,
            z_claims_first=self.z_claims_first,
            cross_step_efficiency=self.cross_step_efficiency)

    def as_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["mesh_shape"] = list(self.mesh_shape)
        d["axis_fits"] = [f.as_dict() for f in self.axis_fits]
        d["samples"] = [s.as_dict() for s in self.samples]
        return d

    @classmethod
    def from_dict(cls, d: dict) -> "CalibrationProfile":
        kw = dict(d)
        kw["mesh_shape"] = tuple(kw.get("mesh_shape", ()))
        kw["axis_fits"] = tuple(AxisFit(**f) for f in kw.get("axis_fits", ()))
        kw["samples"] = tuple(Sample(**s) for s in kw.get("samples", ()))
        known = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in kw.items() if k in known})

    def save(self, path: str) -> str:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path, "w") as f:
            json.dump(self.as_dict(), f, indent=1)
        return path

    @classmethod
    def load(cls, path: str) -> "CalibrationProfile":
        with open(path) as f:
            return cls.from_dict(json.load(f))


def default_path(backend: Optional[str] = None) -> str:
    if backend is None:
        import jax
        backend = jax.default_backend()
    return os.path.join(DEFAULT_DIR, f"{backend}.json")


def resolve(spec: Optional[str]) -> Optional[CalibrationProfile]:
    """``--calib`` semantics: None -> None, 'auto' -> the live backend's
    ``runs/calib/<backend>.json`` if present (None otherwise — an
    uncalibrated run must keep working), else a profile path (must
    exist)."""
    if not spec:
        return None
    if spec == "auto":
        p = default_path()
        return CalibrationProfile.load(p) if os.path.exists(p) else None
    return CalibrationProfile.load(spec)


def resolve_hw(spec: Optional[str]) -> CM.HardwareParams:
    """HardwareParams for a ``--calib`` value; the TPU_V5E guesses when
    uncalibrated (the bitwise-unchanged degenerate point)."""
    prof = resolve(spec)
    return prof.hardware_params() if prof is not None else CM.TPU_V5E


def merge_drift(profile: CalibrationProfile, record: Dict
                ) -> CalibrationProfile:
    """Fold a telemetry drift record (``launch.telemetry.DriftMonitor
    .record()``) into the profile's ``probes``.

    Keyed per workload (``drift:<workload>``) so each (arch, mesh) run
    overwrites its own entry while ``drift_ratio`` tracks the latest
    aggregate. The fitted α/β/γ constants are deliberately NOT rescaled
    here — a drifting end-to-end ratio says the model is wrong for this
    workload, not which constant is wrong; the recorded ratio is the
    evidence a recalibration (benchmarks.calibrate) acts on, and readers
    of the JSON (dryrun/hillclimb) can surface it next to predictions."""
    for field in ("ratio", "predicted_s", "n"):
        if field not in record:
            raise ValueError(f"drift record missing {field!r}: {record}")
    probes = dict(profile.probes)
    key = str(record.get("workload") or "step")
    probes[f"drift:{key}"] = float(record["ratio"])
    probes["drift_ratio"] = float(record["ratio"])
    probes["drift_n"] = float(record["n"])
    return dataclasses.replace(profile, probes=probes)


def merge_probes(profile: CalibrationProfile, records: Sequence[Dict]
                 ) -> CalibrationProfile:
    """Fold a batch of drift records into ``profile.probes`` — the
    per-collective-class verdicts of ``launch.probes.CollectiveProbes``
    (workloads ``collective:<class>``) land as ``drift:collective:<class>``
    keys next to the whole-step ``drift:<workload>`` entries."""
    for rec in records:
        profile = merge_drift(profile, rec)
    return profile


# ---------------------------------------------------------------------- #
# Microbenchmark harness (host-backend timings; needs >= 2 devices)
# ---------------------------------------------------------------------- #

def _timeit(fn, *args, reps: int = 5, warmup: int = 2) -> float:
    """min-of-reps wall time of a jitted call (min rejects scheduler
    noise — the fit wants the deterministic α/β floor)."""
    import jax
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def _axis_label(axis) -> str:
    return "+".join(axis) if isinstance(axis, tuple) else axis


def _axis_p(mesh, axis) -> int:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    names = axis if isinstance(axis, tuple) else (axis,)
    return int(math.prod(sizes[n] for n in names))


def _collective_fns(mesh, axis):
    """Jitted shard_map wrappers of each timed collective over ``axis``
    (a mesh axis name or a tuple of names — the flattened ring).

    Inputs/outputs follow comm_model's buffer conventions: the argument
    of ``all_gather`` is the 1/p shard of the full buffer, of
    ``reduce_scatter``/``all_reduce``/``psum`` the rank's full-size
    partial."""
    import jax
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from repro.core import mesh as M

    def wrap(body, in_spec, out_spec):
        return jax.jit(shard_map(body, mesh=mesh, in_specs=(in_spec,),
                                 out_specs=out_spec, check_vma=False))

    p_ax = math.prod(
        dict(zip(mesh.axis_names, mesh.devices.shape))[n]
        for n in (axis if isinstance(axis, tuple) else (axis,)))

    def ring_exchange(v):
        # the ring-attention KV schedule: each rank's block circulates
        # the whole ring, one ppermute hop at a time, every hop consumed
        # (the sum stands in for the hop's partial-attention read)
        cur, acc = v, v
        for _ in range(p_ax - 1):
            cur = M.ppermute_ring(cur, axis)
            acc = acc + cur
        return acc

    return {
        "all_gather": wrap(lambda v: M.ring_all_gather(v, axis, dim=0),
                           P(axis), P(None)),
        "reduce_scatter": wrap(lambda v: M.ring_reduce_scatter(v, axis,
                                                               dim=0),
                               P(None), P(axis)),
        "all_reduce": wrap(lambda v: M.ring_all_reduce(v, axis, dim=0),
                           P(None), P(None)),
        "psum": wrap(lambda v: M.psum(v, axis), P(None), P(None)),
        "ring_exchange": wrap(ring_exchange, P(axis), P(axis)),
        # all_to_all: each rank holds the full buffer, exchanges the
        # (p-1)/p of it destined elsewhere (pairwise ppermute ring)
        "all_to_all": wrap(lambda v: M.ring_all_to_all(v, axis, dim=0),
                           P(None), P(None)),
    }


def measure_axis(mesh, axis, sizes: Sequence[int], *,
                 dtype=None, reps: int = 5) -> List[Sample]:
    """Time every collective kind over ``axis`` (name or tuple of names)
    across ``sizes`` (buffer elements, comm_model conventions: full
    buffer for AG/RS, reduced buffer for AR/psum)."""
    import jax
    import jax.numpy as jnp

    dtype = dtype or jnp.float32
    p = _axis_p(mesh, axis)
    if p <= 1:
        return []
    fns = _collective_fns(mesh, axis)
    itemsize = jnp.dtype(dtype).itemsize
    # harness floor: a jitted identity pays the Python->runtime dispatch
    # the timing loop itself costs but an *in-program* collective never
    # does — subtract it so γ means per-collective cost, not per-jit-call
    ident = jax.jit(lambda v: v)
    out: List[Sample] = []
    for n in sizes:
        n = int(math.ceil(n / p) * p)  # AG/RS need p | elems
        full = jnp.arange(n, dtype=dtype)
        t0 = _timeit(ident, full, reps=reps)
        shard_arg = {"all_gather": full, "reduce_scatter": full,
                     "all_reduce": full, "psum": full,
                     "ring_exchange": full, "all_to_all": full}
        for kind in _KINDS:
            t = max(_timeit(fns[kind], shard_arg[kind], reps=reps) - t0,
                    0.0)
            steps, wire = collective_geometry(kind, p, n * itemsize)
            out.append(Sample(kind=kind, axis=_axis_label(axis), p=p,
                              elems=n, steps=steps, wire_bytes=wire,
                              seconds=t))
    return out


def measure_gemm(sizes: Sequence[int] = (256, 512, 1024), *,
                 reps: int = 5) -> float:
    """Achieved matmul FLOP/s (best over the size sweep)."""
    import jax
    import jax.numpy as jnp

    best = 0.0
    mm = jax.jit(lambda a, b: a @ b)
    for n in sizes:
        a = jnp.ones((n, n), jnp.float32)
        b = jnp.ones((n, n), jnp.float32)
        t = _timeit(mm, a, b, reps=reps)
        best = max(best, 2.0 * n ** 3 / t)
    return best


def _hidden_fraction(t_comm: float, t_mm: float, t_both: float) -> float:
    """Fraction of the shorter leg the fused program hid: 1.0 means the
    rings rode entirely under the matmul, 0.0 means fully serialized."""
    denom = min(t_comm, t_mm)
    if denom <= 0:
        return 0.0
    return max(0.0, min(1.0, (t_comm + t_mm - t_both) / denom))


def overlap_probe(mesh, axis: str, *, elems: int = 1 << 16,
                  mm_n: int = 512, reps: int = 5) -> Dict[str, float]:
    """Measured comm/compute overlap: ring hops issued alongside an
    *independent* matmul vs back-to-back.

    Probes the z-weight pattern (all-gather ring under a GEMM) and the
    x/y-activation pattern (all-reduce ring under a GEMM) separately:
    their hidden fractions decide ``overlap_efficiency`` (the max — the
    window the scheduler proved it can use) and ``z_claims_first``
    (keep the z-first claim order unless the AR ring demonstrably hides
    better; ``layer_time`` consults the verdict)."""
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from repro.core import mesh as M

    p = int(dict(zip(mesh.axis_names, mesh.devices.shape))[axis])
    if p <= 1:
        return {}
    elems = int(math.ceil(elems / p) * p)
    v = jnp.arange(elems, dtype=jnp.float32)
    a = jnp.ones((mm_n, mm_n), jnp.float32)

    def probe(ring_body):
        ring = jax.jit(shard_map(ring_body, mesh=mesh, in_specs=(P(None),),
                                 out_specs=P(None), check_vma=False))
        mm = jax.jit(lambda x: x @ x)
        both_body = shard_map(ring_body, mesh=mesh, in_specs=(P(None),),
                              out_specs=P(None), check_vma=False)
        both = jax.jit(lambda x, y: (both_body(x), y @ y))
        t_ring = _timeit(ring, v, reps=reps)
        t_mm = _timeit(mm, a, reps=reps)
        t_both = _timeit(both, v, a, reps=reps)
        return t_ring, t_mm, t_both, _hidden_fraction(t_ring, t_mm, t_both)

    zr, zm, zb, z_hidden = probe(
        lambda x: M.ring_all_gather(
            x.reshape(p, -1)[M.axis_index(axis)], axis, dim=0))
    ar, am, ab, ar_hidden = probe(
        lambda x: M.ring_all_reduce(x, axis, dim=0))
    return {"axis": p, "z_ring_s": zr, "z_mm_s": zm, "z_both_s": zb,
            "z_hidden": z_hidden, "ar_ring_s": ar, "ar_mm_s": am,
            "ar_both_s": ab, "ar_hidden": ar_hidden}


def cross_step_probe(mesh, axis: str, *, elems: int = 1 << 16,
                     mm_n: int = 512, reps: int = 5) -> Dict[str, float]:
    """Measured cross-step window: a step's *terminal* all-gather fused
    with the (independent) next step's first matmul vs run sequentially.
    The hidden fraction calibrates ``cross_step_efficiency`` — how much
    of the terminal collectives ``comm_model.dp_sync_time``'s
    ``cross_step`` window may actually claim."""
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from repro.core import mesh as M

    p = int(dict(zip(mesh.axis_names, mesh.devices.shape))[axis])
    if p <= 1:
        return {}
    elems = int(math.ceil(elems / p) * p)
    v = jnp.arange(elems, dtype=jnp.float32)
    a = jnp.ones((mm_n, mm_n), jnp.float32)

    ag_body = shard_map(
        lambda x: M.ring_all_gather(x.reshape(p, -1)[M.axis_index(axis)],
                                    axis, dim=0),
        mesh=mesh, in_specs=(P(None),), out_specs=P(None), check_vma=False)
    ag = jax.jit(ag_body)
    mm = jax.jit(lambda x: x @ x)
    fused = jax.jit(lambda x, y: (ag_body(x), y @ y))
    t_ag = _timeit(ag, v, reps=reps)
    t_mm = _timeit(mm, a, reps=reps)
    t_fused = _timeit(fused, v, a, reps=reps)
    return {"ag_s": t_ag, "next_mm_s": t_mm, "fused_s": t_fused,
            "hidden": _hidden_fraction(t_ag, t_mm, t_fused)}


def run_calibration(mesh=None, *, sizes: Sequence[int] = (1 << 12, 1 << 14,
                                                          1 << 16, 1 << 18),
                    reps: int = 5, quick: bool = False
                    ) -> CalibrationProfile:
    """Time the primitives on the live backend and fit a profile.

    ``mesh`` defaults to a 4D smoke mesh over all host devices (z mapped
    when the device count allows). ``quick`` shrinks the sweep for CI."""
    import jax

    from repro.launch import mesh as LM

    if quick:
        sizes, reps = tuple(sizes[:3]), max(2, reps - 3)
    if mesh is None:
        n = jax.device_count()
        if n < 2:
            raise RuntimeError(
                "calibration needs >= 2 devices (set XLA_FLAGS="
                "--xla_force_host_platform_device_count=8 on CPU)")
        shape = {8: (1, 2, 2, 2), 4: (1, 1, 2, 2), 2: (2, 1, 1, 1)}.get(
            n, (n // 2, 1, 2, 1))
        mesh = LM.make_smoke_mesh(shape, ("data", "x", "y", "z"))

    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    mapped = [ax for ax, p in axis_sizes.items() if p > 1]
    sweep_axes: List = list(mapped)
    if len(mapped) >= 2:
        # the flattened tuple ring (p = product) adds a second hop count
        # to the sample set, separating γ (per call) from α (per hop)
        sweep_axes.append(tuple(mapped[:2]))
    samples: List[Sample] = []
    fits: List[AxisFit] = []
    for axis in sweep_axes:
        ax_samples = measure_axis(mesh, axis, sizes, reps=reps)
        samples.extend(ax_samples)
        g, a, b, r2 = fit_constants(ax_samples)
        fits.append(AxisFit(axis=_axis_label(axis), p=_axis_p(mesh, axis),
                            alpha=a, beta=b, r2=r2,
                            n_samples=len(ax_samples), gamma=g))
    gamma, alpha, beta, r2 = fit_constants(samples)
    flops = measure_gemm(reps=reps)

    # probe the widest mapped axis (most ring hops = clearest signal)
    probe_axis = max(mapped, key=lambda ax: axis_sizes[ax])
    ov = overlap_probe(mesh, probe_axis, reps=reps)
    cs = cross_step_probe(mesh, probe_axis, reps=reps)
    overlap_eff = max(ov.get("z_hidden", 0.0), ov.get("ar_hidden", 0.0))
    # keep the z-first prior unless the AR ring hides strictly better by
    # a >10% (absolute) margin — CPU-noise ties must not flip the order
    z_first = ov.get("ar_hidden", 0.0) <= ov.get("z_hidden", 0.0) + 0.10

    probes = {f"overlap_{k}": float(x) for k, x in ov.items()}
    probes.update({f"cross_step_{k}": float(x) for k, x in cs.items()})
    return CalibrationProfile(
        backend=jax.default_backend(),
        n_devices=int(mesh.devices.size),
        mesh_shape=tuple(int(s) for s in mesh.devices.shape),
        alpha=alpha, gamma=gamma,
        link_bw=(1.0 / beta if beta > 0 else CM.TPU_V5E.link_bw),
        flops=flops, overlap_efficiency=overlap_eff,
        z_claims_first=z_first,
        cross_step_efficiency=cs.get("hidden", 1.0),
        fit_r2=r2, axis_fits=tuple(fits), probes=probes,
        samples=tuple(samples),
        created=time.strftime("%Y-%m-%dT%H:%M:%S"))


# ---------------------------------------------------------------------- #
# Model validation: predicted vs measured rank correlation
# ---------------------------------------------------------------------- #

def spearman(xs: Sequence[float], ys: Sequence[float]) -> float:
    """Spearman rank correlation (Pearson on average ranks; no scipy)."""
    def ranks(v):
        order = np.argsort(np.asarray(v, dtype=np.float64))
        r = np.empty(len(v), dtype=np.float64)
        r[order] = np.arange(len(v), dtype=np.float64)
        # average ties so equal times share a rank
        vv = np.asarray(v, dtype=np.float64)
        for u in np.unique(vv):
            m = vv == u
            r[m] = r[m].mean()
        return r
    rx, ry = ranks(xs), ranks(ys)
    sx, sy = rx.std(), ry.std()
    if sx == 0 or sy == 0:
        return 0.0
    return float(np.mean((rx - rx.mean()) * (ry - ry.mean())) / (sx * sy))
