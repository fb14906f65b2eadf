"""Step builders: jitted, shard_map'ped train / prefill / decode steps.

This is the runtime core every entry point shares (smoke tests, the
dry-run, the training driver, the serving driver). Everything inside the
mapped functions is *manual* SPMD: local shards + the paper's explicit
collectives (core.parallel); the specs computed here are the single source
of truth for how global arrays are laid out.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import gradsync as GS
from repro.core import mesh as M
from repro.core import parallel as PP
from repro.core import trace
from repro.core.gradsync import GradSyncConfig
from repro.core.overdecompose import split_batch
from repro.core.overlap import OverlapConfig
from repro.core.partition import ParamSpec, expert_reduce_grads, \
    spec_tree_to_pspecs, unbox, z_reduce_grads
from repro.models import decoder as D
from repro.models import encdec as ED
from repro.models.base import ArchConfig
from repro.optim import adamw as OPT


# ---------------------------------------------------------------------- #
# model init (boxed -> (params, specs))
# ---------------------------------------------------------------------- #

def init_model(cfg: ArchConfig, axes: M.MeshAxes, key=None, *,
               dtype=jnp.bfloat16, abstract: bool = False):
    if key is None:
        key = jax.random.PRNGKey(0)
    if cfg.arch_type == "audio":
        boxed = ED.encdec_init(key, cfg, axes, dtype=dtype,
                               abstract=abstract)
    else:
        boxed = D.decoder_init(key, cfg, axes, dtype=dtype,
                               abstract=abstract)
    return unbox(boxed)


def init_sharded(cfg: ArchConfig, mesh: Mesh, axes: M.MeshAxes, key, *,
                 dtype=jnp.bfloat16):
    """``init_model`` placed on ``mesh`` as it is computed: one jitted
    program whose outputs carry the parameters' shardings, so each device
    computes only its own shards and none holds the whole model (the
    eager ``init_model`` materializes every leaf on one device first).
    The values are those of ``init_model`` with the same key."""
    structs, specs = init_model(cfg, axes, abstract=True, dtype=dtype)
    shardings = jax.tree.map(lambda _, s: NamedSharding(mesh, s), structs,
                             spec_tree_to_pspecs(specs))
    params = jax.jit(lambda k: init_model(cfg, axes, k, dtype=dtype)[0],
                     out_shardings=shardings)(key)
    return params, specs


# ---------------------------------------------------------------------- #
# batch specs
# ---------------------------------------------------------------------- #

def batch_struct(cfg: ArchConfig, axes: M.MeshAxes, global_batch: int,
                 seq: int, *, kind: str = "train",
                 dtype=jnp.bfloat16):
    """GLOBAL ShapeDtypeStructs + PartitionSpecs for one batch."""
    bax = axes.batch_axes()
    bspec = axes.pspec(bax, None)
    # training tokens/labels also shard their seq dim over the context-
    # parallel axis (None when unmapped — same spec as before). The
    # global array must be fed in the *striped* layout: stripe_batch
    # below / core.mesh.stripe_seq, so rank r's contiguous shard holds
    # global positions {r, r + g_seq, ...} for causal load balance.
    tspec = axes.pspec(bax, axes.seq) if kind == "train" else bspec
    toks = jax.ShapeDtypeStruct((global_batch, seq), jnp.int32)
    out: Dict[str, Tuple[Any, P]] = {"tokens": (toks, tspec)}
    if kind == "train":
        out["labels"] = (toks, tspec)
    if cfg.arch_type == "vlm" and kind in ("train", "prefill"):
        ec = cfg.encoder
        out["image_embeds"] = (
            jax.ShapeDtypeStruct((global_batch, ec.n_ctx, ec.input_dim),
                                 dtype), axes.pspec(bax, None, None))
    if cfg.arch_type == "audio" and kind in ("train", "prefill"):
        ec = cfg.encoder
        out["frames"] = (
            jax.ShapeDtypeStruct((global_batch, ec.n_ctx, cfg.d_model),
                                 dtype), axes.pspec(bax, None, axes.x))
    return out


def stripe_batch(batch, axes: M.MeshAxes):
    """Host-side striping of a global train batch for context
    parallelism: permutes tokens/labels along seq so the contiguous
    per-rank shards of ``batch_struct``'s specs carry the striped
    layout decoder_hidden expects. No-op when seq is unmapped; the
    LM loss is a per-token mean, so the permutation is loss-neutral."""
    p = axes.gseq
    if p <= 1:
        return batch
    out = dict(batch)
    for k in ("tokens", "labels"):
        if k in out:
            out[k] = M.stripe_seq(out[k], p, dim=1)
    return out


def _structs(tree):
    return jax.tree.map(lambda t: t[0], tree,
                        is_leaf=lambda t: isinstance(t, tuple)
                        and len(t) == 2)


def _pspecs(tree):
    return jax.tree.map(lambda t: t[1], tree,
                        is_leaf=lambda t: isinstance(t, tuple)
                        and len(t) == 2)


# ---------------------------------------------------------------------- #
# train step
# ---------------------------------------------------------------------- #

@dataclasses.dataclass(frozen=True)
class TrainOptions:
    overdecompose: int = 2      # paper §4.2 (2 batch-shards); 1 = off
    remat: bool = True
    remat_policy: str = "full"  # full | dots (save matmul outputs)
    xent_chunks: int = 1
    dtype: Any = jnp.bfloat16
    unroll_layers: bool = False  # exact HLO costs for the dry-run
    mtp_weight: float = 0.0      # DeepSeek MTP loss weight (0 = off)
    # ring-decomposed collective matmuls + weight-gather caching
    # (core/overlap.py; rides down to the layers via axes.with_overlap).
    # tp_matmul's z weight gathers and fp32 gradient reduce-scatters ring
    # under its GEMMs; with z unmapped the ring is inert, and the step
    # compiles to the blocking schedule that OverlapConfig() selects.
    overlap: OverlapConfig = OverlapConfig(matmul=True)
    # data-parallel gradient sync: bucketed ring reduce-scatter streamed
    # through the overdecompose loop, optionally with ZeRO-1 data-axis
    # sharding of the AdamW state (core/gradsync.py)
    gradsync: GradSyncConfig = GradSyncConfig()


def _loss_fn(cfg: ArchConfig, axes: M.MeshAxes, opts: TrainOptions,
             pstream=None):
    if cfg.arch_type == "audio":
        assert pstream is None  # zero3 is gated to the decoder families
        def f(params, batch):
            return ED.encdec_loss(params, cfg, axes, batch["frames"],
                                  batch["tokens"], batch["labels"],
                                  unroll=opts.unroll_layers)
        return f

    def f(params, batch):
        return D.lm_loss(params, cfg, axes, batch["tokens"],
                         batch["labels"],
                         image_embeds=batch.get("image_embeds"),
                         remat=opts.remat, xent_chunks=opts.xent_chunks,
                         unroll=opts.unroll_layers,
                         remat_policy=opts.remat_policy,
                         mtp_weight=opts.mtp_weight, pstream=pstream)
    return f


def _stack_of(path, local_shape) -> int:
    """Scan-stack detector for the ZeRO-3 leaf plan: every leaf under
    the decoder's ``segments`` subtree is stacked ``(n_periods, ...)``
    for the layer scan — its shard must keep that leading dim so the
    scan can slice per-layer shard rows."""
    keys = [str(getattr(k, "key", getattr(k, "name", ""))) for k in path]
    if keys and keys[0] == "segments" and len(local_shape) > 0:
        return int(local_shape[0])
    return 1


def _zero3_plan(structs, specs, axes: M.MeshAxes):
    return GS.make_leaf_plan(structs, specs, axes,
                             no_decay=OPT._no_decay, stack_of=_stack_of)


def make_train_step(cfg: ArchConfig, mesh: Mesh, axes: M.MeshAxes,
                    opt_cfg: OPT.AdamWConfig,
                    opts: TrainOptions = TrainOptions()):
    """Returns (jitted_step, param_pspecs, state_pspecs).

    jitted_step(params, opt_state, batch) -> (params, opt_state, metrics).
    """
    axes = axes.with_overlap(opts.overlap)
    structs, specs = init_model(cfg, axes, abstract=True, dtype=opts.dtype)
    pspecs = spec_tree_to_pspecs(specs)
    gs = opts.gradsync
    if axes.gexpert > 1 and gs.enabled:
        raise NotImplementedError(
            "expert parallelism with sharded grad sync (--zero/--zero3/"
            "stream) is not wired yet: the bucket shards lose the "
            "per-param specs the expert-axis reduction needs")
    pstream = None
    if gs.zero3:
        if cfg.arch_type == "audio":
            raise NotImplementedError(
                "gradsync.zero3 (param-shard streaming) is wired for the "
                "decoder families; audio encdec supports zero (ZeRO-1)")
        # ZeRO-3: params live as 1/G_data shards (one stack-aware bucket
        # per leaf); the step's params argument/output IS the shard tree
        plan = _zero3_plan(structs, specs, axes)
        pspecs = GS.param_shard_pspecs(plan, axes)
        spspecs = GS.sharded_state_pspecs(plan, axes)
        pstream = GS.ParamStreamer(plan=plan, axes=axes, ring=gs.ring,
                                   prefetch=gs.prefetch)
    else:
        plan = (GS.make_plan(structs, specs, axes, gs.bucket_bytes,
                             no_decay=OPT._no_decay)
                if gs.enabled else None)
        spspecs = (GS.sharded_state_pspecs(plan, axes) if gs.zero
                   else OPT.state_pspecs(pspecs))
    loss_fn = _loss_fn(cfg, axes, opts, pstream=pstream)

    def scalar_loss(params, batch):
        loss, metrics = loss_fn(params, batch)
        return loss, metrics

    def step(params, opt_state, batch):
        vg = jax.value_and_grad(scalar_loss, has_aux=True)
        n = opts.overdecompose
        stream = gs.enabled and not gs.zero3 and gs.stream
        shards = None
        if n > 1:
            mb = split_batch(batch, n, axes=axes)
            loss = metrics = grads = None
            for i in range(n):
                sub = jax.tree.map(lambda x: x[i], mb)
                if grads is not None and n > 2:
                    # the earlier microbatches' gradients are summed before
                    # this one's forward starts: else XLA may run
                    # microbatches side by side, and memory grows with n at
                    # one microbatch shape. Two keep the free schedule,
                    # which holds one bf16 gradient in place of the sum.
                    grads, sub = jax.lax.optimization_barrier((grads, sub))
                (li, mi), gi = vg(params, sub)
                with trace.layer("update"):
                    loss = li if loss is None else loss + li
                    metrics = mi if metrics is None else jax.tree.map(
                        jnp.add, metrics, mi)
                    if stream:
                        # bucket i's reduce-scatter launches here; microbatch
                        # i+1's backward (next vg call) has no data dependency
                        # on these ring hops, so the latency-hiding scheduler
                        # can run the DP rings under its GEMMs — the same
                        # overlap window the x/y/z rings use. fp32 shard
                        # accumulation doubles as the mixed-precision fix.
                        si = GS.reduce_scatter_grads(gi, plan, axes,
                                                     ring=gs.ring)
                        shards = (si if shards is None
                                  else [a + b for a, b in zip(shards, si)])
                    elif gs.zero3:
                        # zero3: gi is already in the shard layout — each
                        # leaf's gradient came out of the gather's transpose
                        # as a ring reduce-scatter over data, streamed per
                        # layer through this microbatch's own backward
                        si = [g.astype(jnp.float32)
                              for g in jax.tree.leaves(gi)]
                        shards = (si if shards is None
                                  else [a + b for a, b in zip(shards, si)])
                    else:
                        # accumulate in fp32: bf16 running sums lose ~1 ulp
                        # per add, which compounds as overdecompose grows
                        grads = (jax.tree.map(
                            lambda g: g.astype(jnp.float32), gi)
                            if grads is None else jax.tree.map(
                                lambda a, g: a + g.astype(jnp.float32),
                                grads, gi))
            with trace.layer("update"):
                loss = loss / n
                metrics = jax.tree.map(lambda v: v / n, metrics)
                if shards is not None:
                    shards = [s / n for s in shards]
                else:
                    grads = jax.tree.map(lambda g: g / n, grads)
        else:
            (loss, metrics), grads = vg(params, batch)

        with trace.layer("update"):
            return _update(params, opt_state, loss, metrics, grads,
                           shards)

    def _update(params, opt_state, loss, metrics, grads, shards):
        if axes.gseq > 1:
            # params are replicated over seq; each seq-rank's grads hold
            # only its own tokens' contributions (the KV ring transposes
            # back to the local shard), so sum them like a second DP axis
            if shards is not None:
                shards = [M.psum(s, axes.seq) for s in shards]
            elif grads is not None:
                grads = jax.tree.map(lambda g: M.psum(g, axes.seq), grads)

        if axes.gexpert > 1 and grads is not None:
            # expert is a second data axis for dense params (sum like DP)
            # but shards the expert bank (each rank's grad already holds
            # exactly its own experts' contributions): spec-aware
            grads = expert_reduce_grads(grads, specs, axes, M.psum)

        if gs.zero3:
            if shards is None:
                shards = [g.astype(jnp.float32)
                          for g in jax.tree.leaves(grads)]
            shards = GS.tensor_reduce_shards(shards, plan, axes)
            # the new params ARE the cast master shards (rebuild=False):
            # no param rebroadcast — next step's per-layer gathers
            # re-assemble working copies just in time
            params, opt_state, om = OPT.apply_updates_sharded(
                shards, opt_state, plan, axes, opt_cfg, ring=gs.ring,
                rebuild=False)
        elif gs.enabled:
            # bucketed data-parallel sync (core/gradsync.py): scattered
            # fp32 shards + whole-bucket y/z reductions in place of the
            # per-leaf blocking psums
            if shards is None:
                shards = GS.reduce_scatter_grads(grads, plan, axes,
                                                 ring=gs.ring)
            shards = GS.tensor_reduce_shards(shards, plan, axes)
            if gs.zero:
                params, opt_state, om = OPT.apply_updates_sharded(
                    shards, opt_state, plan, axes, opt_cfg, ring=gs.ring)
            else:
                grads = GS.all_gather_grads(shards, plan, axes,
                                            ring=gs.ring)
                params, opt_state, om = OPT.apply_updates(
                    params, grads, opt_state, specs, axes, opt_cfg)
        else:
            # data-parallel gradient all-reduce (paper §3.1) + z reduction
            # for params whose grads are not already z-reduced by their
            # custom vjp
            grads = jax.tree.map(lambda g: M.psum(g, axes.data), grads)
            grads = z_reduce_grads(grads, specs, axes, M.psum)
            params, opt_state, om = OPT.apply_updates(
                params, grads, opt_state, specs, axes, opt_cfg)
        metrics = dict(metrics, loss=loss, **om)
        return params, opt_state, metrics

    bstruct = batch_struct(cfg, axes, 1, 1)  # spec shapes don't matter here
    bpspecs = _pspecs(bstruct)
    mspec = P()
    mkeys = ["loss", "grad_norm", "lr", "xent"]
    if cfg.arch_type != "audio":
        mkeys.append("aux")
        if "moe" in cfg.ffns():
            mkeys += ["moe_held_share", "moe_load_max_mean", "moe_dropped"]
        if opts.mtp_weight > 0 and cfg.mtp_depth > 0:
            mkeys.append("mtp")
    mapped = shard_map(
        step, mesh=mesh,
        in_specs=(pspecs, spspecs, bpspecs),
        out_specs=(pspecs, spspecs, {k: mspec for k in mkeys}),
        check_vma=False)
    return jax.jit(mapped, donate_argnums=(0, 1)), pspecs, spspecs


# ---------------------------------------------------------------------- #
# optimizer-state builders (replicated AdamW vs ZeRO-1 data-sharded)
# ---------------------------------------------------------------------- #

def abstract_opt_state(cfg: ArchConfig, axes: M.MeshAxes,
                       opts: TrainOptions = TrainOptions()):
    """GLOBAL-shaped ShapeDtypeStructs of the optimizer state the train
    step of ``opts`` expects — the sharded-bucket layout under
    ``gradsync.zero``, the replicated per-leaf layout otherwise. The
    dry-run pairs this with ``make_train_step``'s ``spspecs``."""
    axes = axes.with_overlap(opts.overlap)
    structs, specs = init_model(cfg, axes, abstract=True, dtype=opts.dtype)
    gs = opts.gradsync
    if gs.zero3:
        return GS.abstract_sharded_state(_zero3_plan(structs, specs, axes),
                                         axes)
    if gs.zero:
        plan = GS.make_plan(structs, specs, axes, gs.bucket_bytes,
                            no_decay=OPT._no_decay)
        return GS.abstract_sharded_state(plan, axes)
    return OPT.init_state(structs, abstract=True)


def abstract_params(cfg: ArchConfig, axes: M.MeshAxes,
                    opts: TrainOptions = TrainOptions()):
    """(GLOBAL-shaped param structs, PartitionSpecs) in the layout the
    train step of ``opts`` expects: the ZeRO-3 shard tree under
    ``gradsync.zero3``, the replicated-over-data layout otherwise (the
    dry-run pairs this with ``make_train_step``'s param pspecs)."""
    axes = axes.with_overlap(opts.overlap)
    structs, specs = init_model(cfg, axes, abstract=True, dtype=opts.dtype)
    if opts.gradsync.zero3:
        plan = _zero3_plan(structs, specs, axes)
        return GS.abstract_param_shards(plan, axes), \
            GS.param_shard_pspecs(plan, axes)
    return structs, spec_tree_to_pspecs(specs)


def state_layouts(cfg: ArchConfig, axes: M.MeshAxes,
                  opts: TrainOptions = TrainOptions()):
    """((param structs, pspecs), (opt-state structs, pspecs)) of the
    train step of ``opts`` — the persistent per-rank state the ZeRO
    levels shrink; the dry-run prices it per rank for the replicated vs
    ZeRO-1 vs ZeRO-3 memory accounting. One abstract init + one plan
    serves all four trees."""
    axes = axes.with_overlap(opts.overlap)
    structs, specs = init_model(cfg, axes, abstract=True, dtype=opts.dtype)
    pspecs = spec_tree_to_pspecs(specs)
    gs = opts.gradsync
    if gs.zero3:
        plan = _zero3_plan(structs, specs, axes)
        return ((GS.abstract_param_shards(plan, axes),
                 GS.param_shard_pspecs(plan, axes)),
                (GS.abstract_sharded_state(plan, axes),
                 GS.sharded_state_pspecs(plan, axes)))
    if gs.zero:
        plan = GS.make_plan(structs, specs, axes, gs.bucket_bytes,
                            no_decay=OPT._no_decay)
        return ((structs, pspecs),
                (GS.abstract_sharded_state(plan, axes),
                 GS.sharded_state_pspecs(plan, axes)))
    return ((structs, pspecs),
            (OPT.init_state(structs, abstract=True),
             OPT.state_pspecs(pspecs)))


@dataclasses.dataclass(frozen=True)
class GradSyncTools:
    """Jitted companions of a ZeRO-sharded train step.

    ``init(params)`` builds the scattered fp32 state from full
    (replicated-over-data) params; ``gather(state)`` /
    ``scatter(full_state)`` convert to/from the replicated per-leaf
    layout (the checkpoint format — ckpt.py save_sharded/
    restore_sharded); ``plan`` / ``state_pspecs`` are the bucket layout
    and shard_map specs the step was built with. Under ``zero3`` the
    params themselves are sharded too: ``shard_params(full)`` /
    ``unshard_params(shards)`` convert the param tree to/from the shard
    layout (checkpoints stay replicated so g_data can change across
    resume), and ``param_pspecs`` are the shard tree's specs."""

    plan: Any
    state_pspecs: Any
    init: Callable
    gather: Callable
    scatter: Callable
    param_pspecs: Any = None
    shard_params: Optional[Callable] = None
    unshard_params: Optional[Callable] = None


def make_gradsync_tools(cfg: ArchConfig, mesh: Mesh, axes: M.MeshAxes,
                        opts: TrainOptions = TrainOptions()
                        ) -> GradSyncTools:
    """Build the ZeRO state helpers for the same (cfg, mesh, axes, opts)
    a train step was made with (the bucket plan must match)."""
    axes = axes.with_overlap(opts.overlap)
    structs, specs = init_model(cfg, axes, abstract=True, dtype=opts.dtype)
    pspecs = spec_tree_to_pspecs(specs)
    gs = opts.gradsync
    if gs.zero3:
        plan = _zero3_plan(structs, specs, axes)
    else:
        plan = GS.make_plan(structs, specs, axes, gs.bucket_bytes,
                            no_decay=OPT._no_decay)
    sspecs = GS.sharded_state_pspecs(plan, axes)
    fullspecs = OPT.state_pspecs(pspecs)
    init = shard_map(lambda p: GS.init_sharded_state(p, plan, axes),
                     mesh=mesh, in_specs=(pspecs,), out_specs=sspecs,
                     check_vma=False)
    gather = shard_map(lambda s: GS.gather_sharded_state(s, plan, axes),
                       mesh=mesh, in_specs=(sspecs,), out_specs=fullspecs,
                       check_vma=False)
    scatter = shard_map(lambda s: GS.scatter_full_state(s, plan, axes),
                        mesh=mesh, in_specs=(fullspecs,), out_specs=sspecs,
                        check_vma=False)
    extra = {}
    if gs.zero3:
        ppspecs = GS.param_shard_pspecs(plan, axes)
        shard_p = shard_map(lambda p: GS.shard_params(p, plan, axes),
                            mesh=mesh, in_specs=(pspecs,),
                            out_specs=ppspecs, check_vma=False)
        unshard_p = shard_map(
            lambda s: GS.unshard_params(s, plan, axes), mesh=mesh,
            in_specs=(ppspecs,), out_specs=pspecs, check_vma=False)
        extra = dict(param_pspecs=ppspecs,
                     shard_params=jax.jit(shard_p),
                     unshard_params=jax.jit(unshard_p))
    return GradSyncTools(plan=plan, state_pspecs=sspecs,
                         init=jax.jit(init), gather=jax.jit(gather),
                         scatter=jax.jit(scatter), **extra)


# ---------------------------------------------------------------------- #
# elastic snapshot / restore (host replicated layout == checkpoint layout)
# ---------------------------------------------------------------------- #

def snapshot_state(params, opt_state, tools: Optional[GradSyncTools],
                   opts: TrainOptions, *, step: int = 0) -> dict:
    """Host snapshot of the run state in the REPLICATED per-leaf layout.

    This is byte-for-byte the tree ``ckpt.save_sharded`` persists (params
    unsharded under zero3, optimizer state gathered through the same
    jitted ``tools.gather``), kept in memory instead of written to disk —
    the currency of ``MeshLifecycle.reshard``. The plan fingerprint rides
    along so ``restore_state`` can reject a rebuild whose tensor
    partitioning (not just g_data) changed.
    """
    gs = opts.gradsync
    fp = None
    if gs.state_sharded:
        assert tools is not None, "sharded state needs GradSyncTools"
        full_p = tools.unshard_params(params) if gs.zero3 else params
        full_s = tools.gather(opt_state)
        fp = GS.plan_fingerprint(tools.plan)
    else:
        full_p, full_s = params, opt_state
    return {"params": jax.tree.map(np.asarray, jax.device_get(full_p)),
            "opt_state": jax.tree.map(np.asarray, jax.device_get(full_s)),
            "step": int(step), "fingerprint": fp}


def restore_state(snapshot: dict, cfg: ArchConfig, mesh: Mesh,
                  axes: M.MeshAxes, tools: Optional[GradSyncTools],
                  opts: TrainOptions):
    """Re-shard a :func:`snapshot_state` snapshot onto ``(mesh, axes)``.

    Returns ``(params, opt_state)`` in the layout the train step of
    ``opts`` expects on that mesh — sharded through the new mesh's own
    ``scatter``/``shard_params`` tools, i.e. the exact converters
    ``ckpt.restore_sharded`` would use, so restoring from the in-memory
    snapshot and restoring from a checkpoint of the same step are
    bitwise identical.
    """
    axes = axes.with_overlap(opts.overlap)
    structs, specs = init_model(cfg, axes, abstract=True, dtype=opts.dtype)
    pspecs = spec_tree_to_pspecs(specs)
    gs = opts.gradsync
    params = device_put_tree(mesh, snapshot["params"], pspecs)
    if gs.state_sharded:
        assert tools is not None, "sharded state needs GradSyncTools"
        want = snapshot.get("fingerprint")
        if want is not None:
            have = GS.plan_fingerprint(tools.plan)
            if have != want:
                raise ValueError(
                    f"elastic restore: bucket-plan fingerprint {have} != "
                    f"snapshot's {want} — the rebuild changed the tensor "
                    f"partitioning, not just the data axis; the snapshot "
                    f"cannot be re-sharded onto this mesh")
        opt_state = tools.scatter(snapshot["opt_state"])
        if gs.zero3:
            params = tools.shard_params(params)
    else:
        opt_state = device_put_tree(mesh, snapshot["opt_state"],
                                    OPT.state_pspecs(pspecs))
    return params, opt_state


# ---------------------------------------------------------------------- #
# serve steps
# ---------------------------------------------------------------------- #

def make_decode_step(cfg: ArchConfig, mesh: Mesh, axes: M.MeshAxes, *,
                     seqshard: bool = False, dtype=jnp.bfloat16,
                     unroll: bool = False,
                     overlap: OverlapConfig = OverlapConfig()):
    """jitted(params, caches, tokens, pos) -> (logits, caches)."""
    axes = axes.with_overlap(overlap)
    _, specs = init_model(cfg, axes, abstract=True, dtype=dtype)
    pspecs = spec_tree_to_pspecs(specs)
    bspec = axes.pspec(axes.batch_axes(), None)
    if seqshard:
        bspec = P(None, None)  # batch 1: tokens replicated

    if cfg.arch_type == "audio":
        def step(params, caches, tokens, pos):
            return ED.encdec_decode_step(params, cfg, axes, tokens, caches,
                                         pos, unroll=unroll)
    else:
        def step(params, caches, tokens, pos):
            return D.decode_step(params, cfg, axes, tokens, caches, pos,
                                 seqshard=seqshard, unroll=unroll)

    def cspecs(batch_global, seq):
        if cfg.arch_type == "audio":
            return ED.encdec_cache_specs(cfg, axes, batch_global, seq,
                                         dtype=dtype)
        return D.decoder_cache_specs(cfg, axes, batch_global, seq,
                                     seqshard=seqshard, dtype=dtype)

    def build(batch_global, seq):
        ct = cspecs(batch_global, seq)
        cache_pspecs = _pspecs(ct)
        logits_spec = (axes.pspec(axes.batch_axes(), None, axes.y)
                       if not seqshard else axes.pspec(None, None, axes.y))
        mapped = shard_map(
            step, mesh=mesh,
            in_specs=(pspecs, cache_pspecs, bspec, P()),
            out_specs=(logits_spec, cache_pspecs),
            check_vma=False)
        return jax.jit(mapped, donate_argnums=(1,)), ct

    return build, pspecs


def make_paged_step(cfg: ArchConfig, mesh: Mesh, axes: M.MeshAxes, *,
                    dtype=jnp.bfloat16,
                    overlap: OverlapConfig = OverlapConfig()):
    """jitted(params, pools, tokens, positions, q_len, table) ->
    (logits, pools) — the continuous-batching serving step over the
    paged KV cache (launch/serving, docs/serving.md).

    ``build(n_pages_global, page_size)`` returns (fn, pool_tree). Slot
    rows shard over data x z like any batch (their page tables hold each
    shard's LOCAL page ids); KV pools shard pages over data x z and
    heads over y. The engine compiles the same fn at two row widths —
    T = chunk for iterations carrying prefill work, T = 1 for pure
    decode — both against the SAME pool buffers (donated)."""
    axes = axes.with_overlap(overlap)
    _, specs = init_model(cfg, axes, abstract=True, dtype=dtype)
    pspecs = spec_tree_to_pspecs(specs)
    bspec1 = axes.pspec(axes.batch_axes())
    bspec2 = axes.pspec(axes.batch_axes(), None)

    def step(params, pools, tokens, positions, q_len, table):
        return D.paged_step(params, cfg, axes, tokens, pools, positions,
                            q_len, table)

    def build(n_pages_global, page_size):
        ct = D.decoder_paged_cache_specs(cfg, axes, n_pages_global,
                                         page_size, dtype=dtype)
        cache_pspecs = _pspecs(ct)
        logits_spec = axes.pspec(axes.batch_axes(), None, axes.y)
        mapped = shard_map(
            step, mesh=mesh,
            in_specs=(pspecs, cache_pspecs, bspec2, bspec2, bspec1,
                      bspec2),
            out_specs=(logits_spec, cache_pspecs),
            check_vma=False)
        return jax.jit(mapped, donate_argnums=(1,)), ct

    return build, pspecs


def make_prefill_step(cfg: ArchConfig, mesh: Mesh, axes: M.MeshAxes, *,
                      dtype=jnp.bfloat16, unroll: bool = False,
                      overlap: OverlapConfig = OverlapConfig()):
    """jitted(params, caches, batch) -> (last_logits, caches)."""
    axes = axes.with_overlap(overlap)
    _, specs = init_model(cfg, axes, abstract=True, dtype=dtype)
    pspecs = spec_tree_to_pspecs(specs)

    def step(params, caches, batch):
        if cfg.arch_type == "audio":
            enc = ED.encoder_apply(params, cfg, axes, batch["frames"],
                                   unroll=unroll)
            return ED.decoder_apply(params, cfg, axes, batch["tokens"],
                                    enc, mode="prefill", caches=caches,
                                    unroll=unroll)
        return D.prefill(params, cfg, axes, batch["tokens"], caches,
                         image_embeds=batch.get("image_embeds"),
                         unroll=unroll)

    def build(batch_global, seq, cache_seq):
        bt = batch_struct(cfg, axes, batch_global, seq, kind="prefill",
                          dtype=dtype)
        if cfg.arch_type == "audio":
            ct = ED.encdec_cache_specs(cfg, axes, batch_global, cache_seq,
                                       dtype=dtype)
        else:
            ct = D.decoder_cache_specs(cfg, axes, batch_global, cache_seq,
                                       dtype=dtype)
        logits_spec = axes.pspec(axes.batch_axes(), None, axes.y)
        mapped = shard_map(
            step, mesh=mesh,
            in_specs=(pspecs, _pspecs(ct), _pspecs(bt)),
            out_specs=(logits_spec, _pspecs(ct)),
            check_vma=False)
        return jax.jit(mapped, donate_argnums=(1,)), bt, ct

    return build, pspecs


# ---------------------------------------------------------------------- #
# materialization helpers (host -> device with the right shardings)
# ---------------------------------------------------------------------- #

def device_put_tree(mesh: Mesh, values, pspec_tree):
    return jax.tree.map(
        lambda v, s: jax.device_put(v, NamedSharding(mesh, s)),
        values, pspec_tree)


def zeros_caches(mesh: Mesh, cache_tree):
    """Materialize zero-filled caches from a (struct, spec) tree."""
    def one(t):
        st, sp = t
        return jax.device_put(jnp.zeros(st.shape, st.dtype),
                              NamedSharding(mesh, sp))
    return jax.tree.map(one, cache_tree,
                        is_leaf=lambda t: isinstance(t, tuple)
                        and len(t) == 2
                        and isinstance(t[0], jax.ShapeDtypeStruct))
