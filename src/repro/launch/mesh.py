"""Production mesh factories.

``make_production_mesh`` is the assignment-mandated mesh: 16x16
(data, model) per pod, 2x16x16 (pod, data, model) multi-pod. On it the
``model`` axis is bound to the logical ``x`` axis — the Megatron-LM
degenerate point of the paper's algorithm (1D TP), which doubles as the
paper's own baseline.

``make_production_mesh_4d`` factors the same 256/512 devices into
(pod,) data x x x y x z for the paper's 4D decomposition. The factors
default to the communication-model optimum for the given architecture.

``MeshLifecycle`` wraps the same factories in an elastic lifecycle:
device discovery, 6-factor binding, failure tracking, and online
re-sharding of the data axis between steps (grow/shrink ``g_data``
without a process restart — docs/fault_tolerance.md).

Importing this module never touches jax device state: everything is a
function or a lazily-building object.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.core import mesh as M


def _mk(shape, names, devices=None):
    # Auto axes: the step builders place every collective by hand inside
    # shard_map, so no axis may be Explicit (jax.make_mesh's default)
    return jax.make_mesh(tuple(shape), tuple(names),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(names),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def bind_production(mesh, cfg=None) -> M.MeshAxes:
    """Bind the (pod,) data/model mesh to logical axes at the Megatron-LM
    degenerate point: the text's "G_c = G_tensor makes it identical to
    Megatron-LM" — our y = model, x = z = 1. QKV becomes column-parallel,
    the out/down projections row-parallel (all-reduce over y), vocab
    sharded over y: exactly Megatron's schedule.

    Architectures whose head counts cannot use a 16-way y axis (whisper's
    12 heads, xlstm's 4) fall back to the x-degenerate 1D point
    (G_r = G_tensor): feature-sharded weights, all-reduce over x — the
    other corner of the paper's Fig. 5 sweep."""
    data = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
    axes_y = M.bind_axes(mesh, data=data, y="model")
    if cfg is None or cfg.axes_ok(axes_y) is None:
        return axes_y
    axes_x = M.bind_axes(mesh, data=data, x="model")
    if cfg.axes_ok(axes_x) is None:
        return axes_x
    raise ValueError(f"{cfg.name}: no 1D binding fits the production mesh "
                     f"({cfg.axes_ok(axes_y)}; {cfg.axes_ok(axes_x)})")


def make_production_mesh_4d(g_data: int, g_x: int, g_y: int, g_z: int,
                            g_seq: int = 1, g_expert: int = 1, *,
                            multi_pod: bool = False):
    """(pod,) data x x x y x z (x seq) (x expert) with the same device
    counts (256 / 512). ``g_seq`` and ``g_expert`` join the product
    (context and expert parallelism are 5th/6th factors of the same
    budget) and only appear as mesh axes when > 1, so every 4-factor
    caller keeps its exact old mesh."""
    per_pod = g_data * g_x * g_y * g_z * g_seq * g_expert
    assert per_pod == 256, \
        f"4D factors must multiply to 256 per pod, got {per_pod}"
    shape: Tuple[int, ...] = (g_data, g_x, g_y, g_z)
    names: Tuple[str, ...] = ("data", "x", "y", "z")
    if g_seq > 1:
        shape += (g_seq,)
        names += ("seq",)
    if g_expert > 1:
        shape += (g_expert,)
        names += ("expert",)
    if multi_pod:
        return _mk((2,) + shape, ("pod",) + names)
    return _mk(shape, names)


def bind_4d(mesh) -> M.MeshAxes:
    seq = "seq" if "seq" in mesh.axis_names else None
    expert = "expert" if "expert" in mesh.axis_names else None
    if "pod" in mesh.axis_names:
        return M.bind_axes(mesh, data=("pod", "data"), x="x", y="y", z="z",
                           seq=seq, expert=expert)
    return M.bind_axes(mesh, data=("data",), x="x", y="y", z="z", seq=seq,
                       expert=expert)


def make_smoke_mesh(shape: Tuple[int, ...] = (2, 2, 2, 1),
                    names=("data", "x", "y", "z")):
    """Small host-device mesh for CPU tests (requires
    XLA_FLAGS=--xla_force_host_platform_device_count set by the caller)."""
    return _mk(shape, names)


# ---------------------------------------------------------------------- #
# elastic mesh lifecycle
# ---------------------------------------------------------------------- #

@dataclasses.dataclass(frozen=True)
class ElasticState:
    """What :meth:`MeshLifecycle.reshard` hands back to the train loop:
    the rebuilt mesh/axes plus the run state re-sharded onto them (in the
    layout the step function of the run's ``TrainOptions`` expects)."""

    mesh: Any
    axes: M.MeshAxes
    tools: Any          # launch.steps.GradSyncTools (None when unsharded)
    params: Any
    opt_state: Any


class MeshLifecycle:
    """Owns the device pool and the 6-factor mesh across a run's life.

    States::

        init --build()--> active --mark_failed()--> degraded
        degraded/active --reshard()/rebuild()--> active   (generation+1)
        any --stop()--> stopped

    The lifecycle only ever changes **g_data**: the tensor factors
    (g_x, g_y, g_z, g_seq, g_expert) shard *within* a model replica
    (the expert axis holds a share of the expert bank), so losing a
    rank of a replica kills the whole replica — the natural elastic
    move is dropping (or re-adding) data-parallel replicas.
    :meth:`replan` picks the largest ``g_data`` that fits the surviving
    devices and keeps the global batch divisible by
    ``batch_shards x overdecompose``; :meth:`reshard` then rebuilds the
    mesh over the surviving device prefix and re-shards a host
    replicated-layout snapshot (``launch.steps.snapshot_state``) onto
    it through the exact path checkpoints use — so the online re-shard
    is bitwise-equal to a save/restore round trip by construction.

    Generation 0 on an intact pool builds the byte-identical mesh of
    ``make_smoke_mesh``/``make_production_mesh_4d``: swapping a fixed
    mesh for a lifecycle changes no HLO until a failure actually fires.
    """

    STATES = ("init", "active", "degraded", "resharding", "stopped")

    def __init__(self, g_data: int, g_x: int, g_y: int, g_z: int,
                 g_seq: int = 1, g_expert: int = 1, *,
                 devices: Optional[Sequence] = None):
        self.g_data, self.g_x, self.g_y, self.g_z, self.g_seq = \
            int(g_data), int(g_x), int(g_y), int(g_z), int(g_seq)
        self.g_expert = int(g_expert)
        self._devices = list(devices) if devices is not None else None
        self._failed: set = set()            # device ids marked lost
        self.state = "init"
        self.generation = 0
        self.mesh = None
        self.axes: Optional[M.MeshAxes] = None
        self.log: List[Dict[str, Any]] = []  # lifecycle event records

    # -- device pool ---------------------------------------------------- #

    @property
    def devices(self) -> List:
        if self._devices is None:
            self._devices = list(jax.devices())  # discovery, once
        return self._devices

    @property
    def surviving(self) -> List:
        return [d for d in self.devices if d.id not in self._failed]

    @property
    def failed_ids(self) -> Tuple[int, ...]:
        return tuple(sorted(self._failed))

    @property
    def factors(self) -> Tuple[int, int, int, int, int, int]:
        return (self.g_data, self.g_x, self.g_y, self.g_z, self.g_seq,
                self.g_expert)

    @property
    def required(self) -> int:
        return (self.g_data * self.g_x * self.g_y * self.g_z * self.g_seq
                * self.g_expert)

    @property
    def tensor(self) -> int:
        """Devices per model replica (the factors a rank loss cannot
        shrink)."""
        return self.g_x * self.g_y * self.g_z * self.g_seq * self.g_expert

    def _event(self, event: str, **kw) -> None:
        self.log.append(dict(event=event, state=self.state,
                             generation=self.generation,
                             factors=list(self.factors),
                             surviving=len(self.surviving), **kw))

    # -- state transitions ---------------------------------------------- #

    def build(self):
        """(Re)build the mesh over the surviving device prefix; returns
        ``(mesh, axes)`` and moves to ``active``."""
        if self.state == "stopped":
            raise RuntimeError("MeshLifecycle is stopped")
        need, surv = self.required, self.surviving
        if len(surv) < need:
            raise RuntimeError(
                f"mesh {self.factors} needs {need} devices; only "
                f"{len(surv)} of {len(self.devices)} surviving "
                f"(failed ids: {self.failed_ids})")
        shape: Tuple[int, ...] = (self.g_data, self.g_x, self.g_y, self.g_z)
        names: Tuple[str, ...] = ("data", "x", "y", "z")
        if self.g_seq > 1:
            shape += (self.g_seq,)
            names += ("seq",)
        if self.g_expert > 1:
            shape += (self.g_expert,)
            names += ("expert",)
        self.mesh = _mk(shape, names, devices=surv[:need])
        self.axes = bind_4d(self.mesh)
        self.generation += 1
        self.state = "active"
        self._event("build")
        return self.mesh, self.axes

    def mark_failed(self, n: int = 1, *, ids: Optional[Sequence[int]] = None
                    ) -> Tuple[int, ...]:
        """Record device loss: explicit ``ids``, or the last ``n``
        surviving devices (deterministic, keeps the surviving prefix
        stable). Moves to ``degraded``; the mesh itself is rebuilt by
        the next :meth:`reshard`/:meth:`build`."""
        if ids is None:
            surv = self.surviving
            ids = [d.id for d in surv[len(surv) - int(n):]]
        before = set(self._failed)
        self._failed.update(int(i) for i in ids)
        self.state = "degraded"
        self._event("mark_failed", ids=sorted(set(self._failed) - before))
        return tuple(sorted(set(self._failed) - before))

    def mark_recovered(self, ids: Optional[Sequence[int]] = None) -> None:
        """Clear failure marks (device replaced / transient loss healed);
        the pool can then grow back via :meth:`reshard`."""
        if ids is None:
            self._failed.clear()
        else:
            self._failed.difference_update(int(i) for i in ids)
        if self.mesh is not None and len(self.surviving) >= self.required:
            self.state = "active"
        self._event("mark_recovered")

    def stop(self) -> None:
        self.state = "stopped"
        self._event("stop")

    # -- elastic replanning --------------------------------------------- #

    def replan(self, *, global_batch: Optional[int] = None,
               overdecompose: int = 1) -> Dict[str, int]:
        """Largest feasible ``g_data`` for the surviving device count.

        Feasible means ``g_data x tensor <= surviving`` and — when
        ``global_batch`` is given — the overdecompose divisibility rule
        holds: ``global_batch % (g_data x g_z x g_expert x
        overdecompose) == 0`` (each data x z x expert batch shard splits
        into ``overdecompose`` microbatches;
        ``core.overdecompose.split_batch``)."""
        cap = len(self.surviving) // self.tensor
        if cap < 1:
            raise RuntimeError(
                f"{len(self.surviving)} surviving devices cannot hold one "
                f"model replica (tensor factors x*y*z*seq*expert = "
                f"{self.tensor})")
        for gd in range(cap, 0, -1):
            shards = gd * self.g_z * self.g_expert * overdecompose
            if global_batch is None or global_batch % shards == 0:
                return dict(g_data=gd, g_x=self.g_x, g_y=self.g_y,
                            g_z=self.g_z, g_seq=self.g_seq,
                            g_expert=self.g_expert)
        raise RuntimeError(
            f"no g_data in 1..{cap} divides global batch {global_batch} "
            f"by g_data x g_z({self.g_z}) x overdecompose({overdecompose})")

    def reshard(self, cfg, opts, snapshot, *,
                global_batch: Optional[int] = None,
                overdecompose: Optional[int] = None) -> ElasticState:
        """Online elastic re-shard: replan ``g_data`` for the surviving
        devices, rebuild the mesh, and restore ``snapshot`` (a host
        replicated-layout snapshot from ``launch.steps.snapshot_state``)
        onto it — the in-memory equivalent of a
        ``ckpt.save_sharded``/``restore_sharded`` round trip, bitwise.

        ``cfg``/``opts`` are the run's ArchConfig and TrainOptions; the
        caller rebuilds its jitted step function against the returned
        mesh/axes (a new g_data is a new program either way)."""
        from repro.launch import steps as ST  # lazy: keep import light
        od = (opts.overdecompose if overdecompose is None
              else int(overdecompose))
        new = self.replan(global_batch=global_batch, overdecompose=od)
        old = self.g_data
        self.state = "resharding"
        self._event("reshard", g_data_from=old, g_data_to=new["g_data"])
        self.g_data = new["g_data"]
        mesh, axes = self.build()
        tools = (ST.make_gradsync_tools(cfg, mesh, axes, opts)
                 if opts.gradsync.state_sharded else None)
        params, opt_state = ST.restore_state(snapshot, cfg, mesh, axes,
                                             tools, opts)
        return ElasticState(mesh=mesh, axes=axes, tools=tools,
                            params=params, opt_state=opt_state)


def optimal_4d_factors(cfg, shape, g: int = 256,
                       min_tensor: int = 1) -> Tuple[int, int, int, int]:
    """Pick (g_data, g_x, g_y, g_z) by the paper's communication model."""
    from repro.core import comm_model as CM
    cons = cfg.tp_constraints(shape.global_batch)
    cons = CM.Constraints(
        global_batch=cons.global_batch, x_divides=cons.x_divides,
        y_divides=cons.y_divides, min_tensor=min_tensor)
    tokens = shape.global_batch * shape.seq_len
    best = CM.optimize_decomposition(list(cfg.comm_layers()), tokens, g,
                                     cons, top_k=1)[0][0]
    return best.g_data, best.g_x, best.g_y, best.g_z
