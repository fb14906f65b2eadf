"""End-to-end training driver.

Runs real optimization steps on the current host devices (CPU smoke scale
or a real TPU slice — same code path; only the mesh differs). Examples:

  # ~100M model, a few hundred steps on an 8-device CPU mesh:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
  python -m repro.launch.train --arch qwen3-1.7b --preset 100m \\
      --steps 300 --batch 16 --seq 256 --mesh 2,2,2,1

  # reduced smoke variant of any assigned arch:
  python -m repro.launch.train --arch jamba-v0.1-52b --preset smoke

Fault tolerance (docs/fault_tolerance.md): the mesh is owned by a
``MeshLifecycle``; ``--chaos`` injects deterministic failures
(``core/faultinject.py``) which the recovery loop survives by
checkpoint-or-snapshot restore + online re-shard of the data axis onto
the surviving devices; ``--probe-every`` runs per-collective health
probes (``launch/probes.py``) whose verdicts merge back into the
``--calib`` profile; SIGTERM/SIGINT trigger a final checkpoint and a
clean telemetry close.
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import signal
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.checkpoint import ckpt
from repro.configs import get_config
from repro.core.gradsync import GradSyncConfig
from repro.core.partition import spec_tree_to_pspecs
from repro.data.synthetic import DataConfig, SyntheticText, make_batch
from repro.launch import mesh as LM
from repro.launch.compile_cache import enable_compile_cache
from repro.launch import steps as ST
from repro.optim.adamw import AdamWConfig, init_state
from repro.optim import adamw as OPT


def preset_config(cfg, preset: str):
    """Model-size presets for CPU-scale end-to-end runs."""
    if preset == "full":
        return cfg
    if preset == "smoke":
        return cfg.reduced()
    if preset == "100m":
        # ~100M-param member of the same family
        segs = cfg.segments()
        return dataclasses.replace(
            cfg.reduced(), name=cfg.name + "-100m", d_model=512,
            n_heads=8, n_kv_heads=min(8, cfg.n_kv_heads), head_dim=64,
            d_ff=(2048 if cfg.d_ff else 0), vocab_size=32000,
            n_layers=max(cfg.reduced().n_layers, 4)
            if not cfg.mixer_pattern and cfg.xlstm is None
            else cfg.reduced().n_layers)
    raise ValueError(preset)


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="python -m repro.launch.train",
        description="End-to-end training on the current host devices "
                    "(CPU smoke scale or a real TPU slice).")
    ap.add_argument("--arch", required=True,
                    help="architecture name (repro.configs)")
    ap.add_argument("--preset", default="smoke",
                    choices=["smoke", "100m", "full"],
                    help="model-size preset for CPU-scale runs")
    ap.add_argument("--steps", type=int, default=100,
                    help="optimizer steps")
    ap.add_argument("--batch", type=int, default=8,
                    help="global batch (sequences)")
    ap.add_argument("--seq", type=int, default=128,
                    help="sequence length (tokens)")
    ap.add_argument("--lr", type=float, default=3e-4,
                    help="peak AdamW learning rate")
    ap.add_argument("--mesh", default="2,2,2,1",
                    help="g_data,g_x,g_y,g_z[,g_seq[,g_expert]] over "
                         "host devices (5th/6th factors: context / "
                         "expert parallelism)")
    ap.add_argument("--overdecompose", type=int, default=2,
                    help="microbatch count of the overdecompose loop")
    ap.add_argument("--zero", action="store_true",
                    help="ZeRO-sharded DP sync: bucketed gradient "
                         "reduce-scatter rings streamed through the "
                         "overdecompose loop, AdamW state sharded over "
                         "the data axis (core/gradsync.py)")
    ap.add_argument("--zero3", action="store_true",
                    help="ZeRO-3 param-shard streaming: params live as "
                         "1/G_data shards, each layer's working copy "
                         "ring-all-gathered just-in-time inside the "
                         "layer scan (core/gradsync.py); implies the "
                         "--zero state sharding")
    ap.add_argument("--zero3-prefetch", action="store_true",
                    help="with --zero3: gather layer i+1's shards during "
                         "layer i's compute; the copy is retained for "
                         "the backward (no re-gather, ~full param "
                         "memory)")
    ap.add_argument("--dp-bucket-mb", type=float, default=4.0,
                    help="fp32 gradient bucket bound in MiB "
                         "(with --zero/--zero3)")
    ap.add_argument("--dtype", default="float32",
                    choices=["float32", "bfloat16"],
                    help="activation/param compute dtype")
    ap.add_argument("--calib", default="",
                    help="hardware calibration profile (path or 'auto'; "
                         "benchmarks.calibrate): report the α-β model's "
                         "predicted step time next to the measured one "
                         "at the end of the run")
    ap.add_argument("--ckpt", default="",
                    help="checkpoint path (.npz) to save at the end "
                         "(atomic write + per-leaf checksums; see also "
                         "--ckpt-every / --resume)")
    ap.add_argument("--ckpt-every", type=int, default=0, metavar="N",
                    help="also checkpoint every N steps (0 = off); the "
                         "write is atomic, so a crash mid-save keeps "
                         "the previous checkpoint")
    ap.add_argument("--resume", action="store_true",
                    help="restore from --ckpt before training (verifies "
                         "checksums first) and continue from the saved "
                         "step; the mesh may differ from the saving "
                         "run's — the state re-shards through the "
                         "replicated checkpoint layout")
    ap.add_argument("--chaos", default="", metavar="SPEC",
                    help="deterministic fault injection "
                         "(core/faultinject.py), e.g. 'seed=0;"
                         "rank_loss@5:n=2,via=ckpt;ckpt_corrupt@4;"
                         "timeout@7:class=dp_rs_ag,secs=0.3'. rank_loss "
                         "shrinks g_data online via the mesh lifecycle; "
                         "rank_recover returns the lost capacity and "
                         "grows g_data back the same way; "
                         "ckpt_corrupt damages the --ckpt file in place; "
                         "timeout stalls one collective class so the "
                         "watchdog must classify the step")
    ap.add_argument("--probe-every", type=int, default=0, metavar="N",
                    help="run per-collective health probes every N "
                         "steps (launch/probes.py): one tiny timed "
                         "program per collective class on the mesh, "
                         "drift-monitored against the --calib profile's "
                         "alpha-beta prediction and merged back into "
                         "profile.probes at exit; 0 = off (the default "
                         "keeps the run's HLO byte-identical)")
    ap.add_argument("--log-every", type=int, default=10,
                    help="steps between metric log lines")
    ap.add_argument("--telemetry", action="store_true",
                    help="per-step JSONL telemetry to runs/telemetry/"
                         "<run>.jsonl (launch/telemetry.py): step time "
                         "EMA + p50/p99, tokens/s, MFU, loss/grad-norm, "
                         "peak device bytes, and — with --calib — the "
                         "predicted-vs-measured drift ratio. Blocks on "
                         "each step's metrics, so the host loop "
                         "serializes with the device")
    ap.add_argument("--profile-steps", default="", metavar="A:B",
                    help="capture a jax.profiler trace of steps A..B "
                         "(inclusive) to runs/profiles/<run>/; ops carry "
                         "the layer scopes (vocab, norm, attn, attn_core, "
                         "mlp, update, ...) and ring scopes of "
                         "core/trace.py in their op_name. Scopes are "
                         "always on and never toggled: the persistent "
                         "compile cache ignores metadata, so a toggled "
                         "build could load a scope-free executable")
    ap.add_argument("--log-file", default="",
                    help="telemetry JSONL path (implies --telemetry; "
                         "default runs/telemetry/<run>.jsonl)")
    return ap


def _ckpt_snapshot(path: str, cfg, axes, opts) -> dict:
    """Load a checkpoint into the host replicated-layout snapshot form
    of ``launch.steps.snapshot_state`` — verifying every leaf's checksum
    first, so a corrupt file is rejected with the offending leaf named
    instead of scattering garbage onto the mesh."""
    ckpt.verify(path)
    structs, _ = ST.init_model(cfg, axes.with_overlap(opts.overlap),
                               abstract=True, dtype=opts.dtype)
    like_state = OPT.init_state(structs, abstract=True)
    params, step = ckpt.restore(path, structs)
    state, _ = ckpt.restore(path, like_state, root="opt_state")
    return {"params": params, "opt_state": state, "step": int(step),
            "fingerprint": None}


def main():
    args = build_parser().parse_args()
    enable_compile_cache()

    # resolve the calibration profile up front: a bad --calib path must
    # fail before the training loop, not after it
    calib_hw = None
    if args.calib:
        from repro.core import calibrate as CB
        calib_hw = CB.resolve_hw(args.calib)

    profile_steps = None
    if args.profile_steps:
        a, _, b = args.profile_steps.partition(":")
        profile_steps = (int(a), int(b))
        if not (0 <= profile_steps[0] <= profile_steps[1]):
            raise SystemExit(f"--profile-steps {args.profile_steps}: "
                             f"need 0 <= A <= B")

    injector = None
    if args.chaos:
        from repro.core import faultinject as FI
        injector = FI.parse_chaos(args.chaos)
        print(f"chaos: seed={injector.seed} events="
              f"{[f'{e.kind}@{e.step}' for e in injector.events]}")

    shape = tuple(int(x) for x in args.mesh.split(","))
    life = LM.MeshLifecycle(*shape)
    mesh, axes = life.build()
    cfg = preset_config(get_config(args.arch), args.preset)
    dtype = jnp.float32 if args.dtype == "float32" else jnp.bfloat16

    params, specs = ST.init_sharded(cfg, mesh, axes, jax.random.PRNGKey(0),
                                    dtype=dtype)
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    print(f"arch={cfg.name} params={n_params/1e6:.1f}M "
          f"mesh={shape} devices={mesh.devices.size}")

    pspecs = spec_tree_to_pspecs(specs)
    if args.zero3:
        gs = GradSyncConfig(zero3=True, prefetch=args.zero3_prefetch,
                            bucket_mb=args.dp_bucket_mb)
    elif args.zero:
        gs = GradSyncConfig(zero=True, bucket_mb=args.dp_bucket_mb)
    else:
        gs = GradSyncConfig()
    topts = ST.TrainOptions(overdecompose=args.overdecompose, dtype=dtype,
                            gradsync=gs)
    tools = (ST.make_gradsync_tools(cfg, mesh, axes, topts)
             if gs.state_sharded else None)
    state = tools.init(params) if gs.state_sharded else init_state(params)
    if gs.zero3:
        # the step's params argument IS the 1/G_data shard tree from
        # here on; working copies are streamed per layer inside the step
        params = tools.shard_params(params)
    opt = AdamWConfig(lr=args.lr, warmup_steps=min(20, args.steps // 10 + 1),
                      total_steps=args.steps)
    step_fn, _, _ = ST.make_train_step(cfg, mesh, axes, opt, topts)

    def save_checkpoint(at_step: int) -> None:
        if gs.state_sharded:
            # sharded opt state (and, under zero3, the param shards)
            # travels in the replicated per-leaf layout so the run can
            # resume under a different g_data
            full_p = (tools.unshard_params(params) if gs.zero3
                      else params)
            ckpt.save_sharded(args.ckpt, jax.tree.map(np.asarray, full_p),
                              state, tools.gather, step=at_step,
                              pspecs=pspecs,
                              extra={"dp_bucket_mb": args.dp_bucket_mb,
                                     "zero3": gs.zero3,
                                     "mesh": list(life.factors)})
        else:
            ckpt.save(args.ckpt, jax.tree.map(np.asarray, params),
                      jax.tree.map(np.asarray, jax.device_get(state)),
                      step=at_step, pspecs=pspecs)

    start_step = 0
    if args.resume:
        if not args.ckpt:
            raise SystemExit("--resume needs --ckpt")
        snap = _ckpt_snapshot(args.ckpt, cfg, axes, topts)
        params, state = ST.restore_state(snap, cfg, mesh, axes, tools,
                                         topts)
        start_step = snap["step"] + 1
        print(f"resumed {args.ckpt} at step {snap['step']} "
              f"(mesh {life.factors})")

    data = SyntheticText(DataConfig(vocab_size=cfg.vocab_size,
                                    seq_len=args.seq,
                                    global_batch=args.batch))

    pred = None
    if calib_hw is not None:
        # the α-β model's step time for THIS run, priced with the --calib
        # profile: seeds the drift monitor and the end-of-run print
        from repro.core import comm_model as CM
        hw = dataclasses.replace(
            calib_hw, bytes_per_elem=float(jnp.dtype(dtype).itemsize))
        pred = CM.predict_step_time(
            list(cfg.comm_layers()), args.batch * args.seq,
            CM.Decomposition(*shape), hw, gradsync=gs,
            microbatches=args.overdecompose)

    run_name = f"{cfg.name}-{time.strftime('%Y%m%d-%H%M%S')}"
    telem = None
    if args.telemetry or args.log_file:
        from repro.core import comm_model as CM
        from repro.launch import telemetry as TL
        telem = TL.Telemetry(
            run_name, path=args.log_file or None,
            tokens_per_step=args.batch * args.seq,
            flops_per_token=CM.model_flops_per_token(cfg),
            peak_flops_per_device=TL.peak_flops_per_device(),
            n_devices=int(mesh.devices.size),
            drift=(TL.DriftMonitor(pred.total)
                   if pred is not None and pred.total > 0 else None),
            meta={"arch": cfg.name, "mesh": list(shape),
                  "n_devices": int(mesh.devices.size), "batch": args.batch,
                  "seq": args.seq, "dtype": args.dtype,
                  "calib": args.calib})

    probes = watchdog = None
    PRB = None
    if args.probe_every > 0 or injector is not None:
        # chaos mode always arms the probes/watchdog (the timeout events
        # need something to classify them); with both off nothing here
        # is built and the training step's HLO stays byte-identical
        from repro.launch import probes as PRB
        probes = PRB.CollectiveProbes(mesh, axes, calib_hw,
                                      injector=injector)
        watchdog = PRB.Watchdog(probes)

    # SIGTERM/SIGINT flip a flag; the loop drains the in-flight step,
    # writes a final checkpoint, and closes telemetry cleanly
    stop = {"sig": None}

    def _on_signal(signum, frame):
        stop["sig"] = signum
    old_handlers = {s: signal.signal(s, _on_signal)
                    for s in (signal.SIGTERM, signal.SIGINT)}

    log = []
    t0 = time.time()
    t_warm = None  # set after the compile step (excluded from timing)
    t_step = None  # previous step's end — the per-step telemetry clock
    prof_on = False
    done = 0       # completed steps this process (compile = done 0)
    step = start_step
    while step < args.steps:
        if stop["sig"] is not None:
            sig_name = signal.Signals(stop["sig"]).name
            print(f"caught {sig_name}: shutting down after step "
                  f"{step - 1}", flush=True)
            if telem is not None:
                telem.event(step, "shutdown", sig=sig_name,
                            generation=life.generation)
            break

        if injector is not None:
            rank_loss = None
            rank_recover = None
            for ev in injector.events_at(step):
                if ev.kind == "ckpt_corrupt":
                    target = args.ckpt or ""
                    if target and not os.path.exists(target):
                        target += ".npz"
                    if target and os.path.exists(target):
                        from repro.core import faultinject as FI
                        detail = FI.corrupt_checkpoint(
                            target, seed=injector.seed, step=step,
                            mode=ev.get("mode", "bitflip"))
                        print(f"chaos: ckpt_corrupt@{step}: {detail}",
                              flush=True)
                        if telem is not None:
                            telem.event(step, "ckpt_corrupt",
                                        detail=detail)
                    else:
                        print(f"chaos: ckpt_corrupt@{step}: no "
                              f"checkpoint to corrupt, skipped",
                              flush=True)
                        if telem is not None:
                            telem.event(step, "ckpt_corrupt",
                                        detail="skipped: no checkpoint")
                elif ev.kind == "rank_loss":
                    rank_loss = ev
                elif ev.kind == "rank_recover":
                    rank_recover = ev
            if rank_loss is not None:
                # ---- recovery: shrink the mesh, re-shard, continue ----
                n = int(rank_loss.get("n", "1"))
                via = rank_loss.get("via", "online")
                print(f"chaos: rank_loss@{step}: losing {n} device(s), "
                      f"recover via={via}", flush=True)
                if telem is not None:
                    telem.event(step, "rank_loss", n=n, via=via,
                                generation=life.generation)
                life.mark_failed(n)
                snap = None
                if via == "ckpt" and args.ckpt:
                    try:
                        snap = _ckpt_snapshot(args.ckpt, cfg, axes, topts)
                        print(f"recovering from checkpoint {args.ckpt} "
                              f"(step {snap['step']})", flush=True)
                    except (ckpt.CheckpointError, KeyError, ValueError,
                            OSError) as err:
                        print(f"checkpoint unusable ({err}); falling "
                              f"back to the in-memory snapshot",
                              flush=True)
                        if telem is not None:
                            telem.event(step, "ckpt_unusable",
                                        detail=str(err)[:300])
                if snap is None:
                    snap = ST.snapshot_state(params, state, tools, topts,
                                             step=step - 1)
                es = life.reshard(cfg, topts, snap,
                                  global_batch=args.batch)
                mesh, axes, tools = es.mesh, es.axes, es.tools
                params, state = es.params, es.opt_state
                step_fn, _, _ = ST.make_train_step(cfg, mesh, axes, opt,
                                                   topts)
                if probes is not None:
                    probes = PRB.CollectiveProbes(mesh, axes, calib_hw,
                                                  injector=injector)
                    watchdog = PRB.Watchdog(probes)
                if telem is not None:
                    telem.event(step, "resharded",
                                generation=life.generation,
                                g_data=life.g_data,
                                devices=int(mesh.devices.size))
                print(f"resharded: generation {life.generation}, mesh "
                      f"{life.factors}, {mesh.devices.size} devices",
                      flush=True)
                step = snap["step"] + 1
                done = 0  # the rebuilt step recompiles; re-warm timing
                continue
            if rank_recover is not None:
                # ---- recovery: grow the mesh back, re-shard, continue --
                print(f"chaos: rank_recover@{step}: failed capacity "
                      f"returned, growing g_data back", flush=True)
                if telem is not None:
                    telem.event(step, "rank_recover",
                                generation=life.generation)
                life.mark_recovered()
                snap = ST.snapshot_state(params, state, tools, topts,
                                         step=step - 1)
                es = life.reshard(cfg, topts, snap,
                                  global_batch=args.batch)
                mesh, axes, tools = es.mesh, es.axes, es.tools
                params, state = es.params, es.opt_state
                step_fn, _, _ = ST.make_train_step(cfg, mesh, axes, opt,
                                                   topts)
                if probes is not None:
                    probes = PRB.CollectiveProbes(mesh, axes, calib_hw,
                                                  injector=injector)
                    watchdog = PRB.Watchdog(probes)
                if telem is not None:
                    telem.event(step, "resharded",
                                generation=life.generation,
                                g_data=life.g_data,
                                devices=int(mesh.devices.size))
                print(f"resharded: generation {life.generation}, mesh "
                      f"{life.factors}, {mesh.devices.size} devices",
                      flush=True)
                step = snap["step"] + 1
                done = 0  # the rebuilt step recompiles; re-warm timing
                continue

        if profile_steps and step == profile_steps[0]:
            prof_dir = os.path.join("runs", "profiles", run_name)
            jax.profiler.start_trace(prof_dir)
            prof_on = True
        batch = {k: jnp.asarray(v) for k, v in
                 make_batch(cfg, step, data, dtype=np.float32).items()}
        if dtype == jnp.bfloat16:
            batch = {k: (v.astype(jnp.bfloat16) if v.dtype == jnp.float32
                         else v) for k, v in batch.items()}
        params, state, metrics = step_fn(params, state, batch)
        if injector is not None:
            stall_s = injector.step_stall(step)
            if stall_s > 0:
                jax.block_until_ready(metrics["loss"])
                time.sleep(stall_s)  # the simulated hung collective
        if done == 0:
            jax.block_until_ready(metrics["loss"])
            t_step = t_warm = time.time()
        elif telem is not None or watchdog is not None:
            # per-step wall time needs the step's result on host; the
            # telemetry-off path keeps the async dispatch loop untouched
            jax.block_until_ready(metrics["loss"])
            now = time.time()
            step_s = now - t_step
            if telem is not None:
                telem.train_step(step, step_s,
                                 loss=float(metrics["loss"]),
                                 grad_norm=float(metrics["grad_norm"]))
            if watchdog is not None:
                if watchdog.stalled(step_s):
                    verdict = watchdog.classify(step)
                    print(f"watchdog: step {step} took {step_s * 1e3:.1f}"
                          f" ms (baseline {watchdog.baseline_s * 1e3:.1f}"
                          f" ms) -> {verdict['verdict']}"
                          f" suspects={verdict['suspects']}", flush=True)
                    if telem is not None:
                        telem.event(step, "stalled_step",
                                    step_s=step_s,
                                    baseline_s=watchdog.baseline_s,
                                    verdict=verdict["verdict"],
                                    suspects=verdict["suspects"])
                        for r in verdict["results"].values():
                            telem.probe(step, r)
                    now = time.time()  # classify fired the probes
                else:
                    # a stalled step must not drag the baseline up
                    watchdog.observe(step_s)
            t_step = now
        if (probes is not None and args.probe_every > 0 and done > 0
                and step % args.probe_every == 0):
            for r in probes.run(step).values():
                if telem is not None:
                    telem.probe(step, r)
            t_step = time.time()  # probe time is not step time
        if prof_on and step == profile_steps[1]:
            if telem is None and done > 0:
                jax.block_until_ready(metrics["loss"])
            jax.profiler.stop_trace()
            prof_on = False
            print(f"profile: steps {profile_steps[0]}..{profile_steps[1]} "
                  f"-> runs/profiles/{run_name}", flush=True)
        if step % args.log_every == 0 or step == args.steps - 1:
            loss = float(metrics["loss"])
            gn = float(metrics["grad_norm"])
            if done == 0:
                # the compile step's clock is dominated by tracing +
                # lowering; report as-is
                tok_s = args.batch * args.seq / max(time.time() - t0, 1e-9)
            else:
                # warm clock over the steps since the last (re)compile —
                # dividing by the t0 window would fold compile into
                # steady-state throughput and understate it
                tok_s = (done * args.batch * args.seq
                         / max(time.time() - t_warm, 1e-9))
            print(f"step {step:5d} loss {loss:.4f} gnorm {gn:.3f} "
                  f"{tok_s:,.0f} tok/s", flush=True)
            log.append({"step": step, "loss": loss, "grad_norm": gn,
                        "tok_s": tok_s})
            assert np.isfinite(loss), "NaN loss"
        if (args.ckpt and args.ckpt_every > 0 and step > 0
                and step % args.ckpt_every == 0):
            save_checkpoint(step)
        done += 1
        step += 1
    jax.block_until_ready(params)
    t_end = time.time()  # before the checkpoint write pollutes the clock
    if prof_on:
        # the window ran off the end of the run (B >= steps)
        jax.profiler.stop_trace()
    for s, h in old_handlers.items():
        signal.signal(s, h)

    if args.ckpt and done > 0:
        save_checkpoint(step - 1)
        print("saved", args.ckpt)
    if pred is not None and done > 1:
        # predicted-vs-measured validation line: the α-β model priced
        # with the --calib profile against this run's wall clock
        measured_s = (t_end - t_warm) / (done - 1)
        print(f"calib[{args.calib}]: predicted step "
              f"{pred.total * 1e3:.2f} ms (compute {pred.compute * 1e3:.2f}"
              f" + exposed {pred.exposed_comm * 1e3:.2f}), measured "
              f"{measured_s * 1e3:.2f} ms/step")
    if telem is not None:
        telem.close()
    if args.calib:
        # fold measured/predicted verdicts back into the profile
        # (probes only — the fitted constants stay untouched)
        from repro.core import calibrate as CB
        prof = CB.resolve(args.calib)
        merged = []
        if prof is not None:
            if (telem is not None and telem.drift is not None
                    and telem.drift.n):
                prof = CB.merge_drift(prof, telem.drift.record(
                    workload=f"{cfg.name}@{args.mesh}"))
                merged.append("drift")
            if probes is not None and probes.records():
                prof = CB.merge_probes(prof, probes.records())
                merged.append("probes")
            if merged:
                path = (CB.default_path() if args.calib == "auto"
                        else args.calib)
                prof.save(path)
                print(f"{'+'.join(merged)} record merged into {path}")
    if log:
        print("final loss:", log[-1]["loss"])


if __name__ == "__main__":
    main()
