"""Open-loop serving benchmark: continuous batching vs fixed batching.

One synthetic open-loop workload (Poisson arrivals, uniform prompts,
seeded per-request generation lengths) is served twice at every mesh:

  * ``continuous`` — the paged engine (launch/serving): chunked prefill
    rides the decode step, requests admit/evict every iteration;
  * ``fixed`` — the head-of-line baseline: requests are batched in
    arrival order, each batch prefills together and decodes in lockstep
    until its LONGEST member finishes (finished slots burn compute).

Both paths sample greedy argmax over the full padded vocab, so the
generated ids must match request-for-request — the paged-vs-dense token
parity assert. Continuous must win on tokens/s at the same mesh (it
reclaims the idle decode slots and the head-of-line wait); the run fails
loudly if it does not.

``serve_capacity`` (core/comm_model.py) predicts tokens/s per mesh from
the α-β-γ constants; the report ends with the Spearman rank correlation
of predicted vs measured throughput over the mesh sweep.

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
  PYTHONPATH=src python -m benchmarks.serving

Writes ``runs/perf/serving.csv`` (one row per mesh x mode) and prints
the same rows as ``name,us_per_call,derived`` CSV for benchmarks.run.
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import time
from typing import List, Tuple

import numpy as np

import jax
import jax.numpy as jnp

# mesh sweep: every candidate must factor the host devices exactly and
# keep g_seq == 1 (serving is gated to non-seq-sharded meshes)
MESHES = [("gdata2_gx2_gy2", (2, 2, 2, 1)),
          ("gdata1_gx2_gy2_gz2", (1, 2, 2, 2)),
          ("gdata4_gy2", (4, 1, 2, 1))]


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="python -m benchmarks.serving",
        description="Open-loop serving benchmark: continuous batching "
                    "(paged KV) vs the fixed-batch head-of-line "
                    "baseline, same workload, same meshes, plus the "
                    "serve_capacity predicted-vs-measured rank check.")
    ap.add_argument("--arch", default="qwen3-1.7b",
                    help="architecture name (attention-only decoder)")
    ap.add_argument("--requests", type=int, default=16,
                    help="synthetic requests (rounded up to a multiple "
                         "of --slots)")
    ap.add_argument("--rate", type=float, default=200.0,
                    help="open-loop Poisson arrival rate in requests/s")
    ap.add_argument("--prompt-len", type=int, default=16,
                    help="prompt length in tokens (uniform — the dense "
                         "baseline needs a rectangular prefill)")
    ap.add_argument("--gen-min", type=int, default=4,
                    help="per-request generation length lower bound")
    ap.add_argument("--gen-max", type=int, default=32,
                    help="per-request generation length upper bound")
    ap.add_argument("--slots", type=int, default=8,
                    help="concurrent slots / fixed batch width")
    ap.add_argument("--page-size", type=int, default=8,
                    help="tokens per KV page (continuous mode)")
    ap.add_argument("--pages", type=int, default=48,
                    help="physical KV pages per batch shard (incl. the "
                         "reserved null page)")
    ap.add_argument("--chunk", type=int, default=16,
                    help="prefill chunk rows per mixed step")
    ap.add_argument("--seed", type=int, default=0,
                    help="workload RNG seed")
    ap.add_argument("--calib", default="",
                    help="hardware calibration profile (path or 'auto'; "
                         "benchmarks.calibrate) pricing the "
                         "serve_capacity predictions")
    ap.add_argument("--telemetry", action="store_true",
                    help="per-step scheduler counters as JSONL under "
                         "runs/telemetry/serving-<mesh>.jsonl; the JSONL "
                         "summary carries the engine's tokens/s so it "
                         "agrees with --out by construction")
    ap.add_argument("--out", default="runs/perf/serving.csv",
                    help="per-mesh results CSV path")
    return ap


def _workload(args, vocab: int) -> list:
    """Seeded open-loop request list (shared by both serving modes)."""
    from repro.launch.serving import Request
    n = -(-args.requests // args.slots) * args.slots
    rng = np.random.RandomState(args.seed)
    t, reqs = 0.0, []
    for i in range(n):
        t += float(rng.exponential(1.0 / args.rate))
        reqs.append(Request(
            rid=i,
            prompt=rng.randint(1, vocab,
                               size=(args.prompt_len,)).astype(np.int32),
            max_new=int(rng.randint(args.gen_min, args.gen_max + 1)),
            arrival=t))
    return reqs


def _fresh(reqs: list) -> list:
    """Per-mode copies — the scheduler mutates request state in place."""
    import copy
    out = []
    for r in reqs:
        c = copy.copy(r)
        c.generated, c.pages = [], []
        c.state, c.slot, c.pos = "queued", -1, 0
        c.t_first = c.t_done = -1.0
        c.preemptions, c.admit_seq = 0, -1
        out.append(c)
    return out


def _setup_model(arch: str, shape):
    from repro.configs import get_config
    from repro.core.partition import spec_tree_to_pspecs
    from repro.launch import mesh as LM
    from repro.launch import steps as ST

    mesh = LM.make_smoke_mesh(shape, ("data", "x", "y", "z"))
    axes = LM.bind_4d(mesh)
    cfg = get_config(arch).reduced()
    params, specs = ST.init_model(cfg, axes, jax.random.PRNGKey(0),
                                  dtype=jnp.float32)
    params = ST.device_put_tree(mesh, params, spec_tree_to_pspecs(specs))
    return cfg, mesh, axes, params


def run_fixed_baseline(cfg, mesh, axes, params, reqs, args):
    """Head-of-line fixed batching: arrival-order batches of ``slots``
    prefill together, then decode lockstep until the longest member is
    done. Fills each request's ``generated``/timing fields; returns a
    ServeStats like the engine's."""
    from repro.launch import steps as ST
    from repro.launch.serving.engine import ServeStats, percentiles

    B, L = args.slots, args.prompt_len
    S_max = L + max(r.max_new for r in reqs)
    pre_build, _ = ST.make_prefill_step(cfg, mesh, axes, dtype=jnp.float32)
    pre_fn, _, ct = pre_build(B, L, S_max)
    dec_build, _ = ST.make_decode_step(cfg, mesh, axes, dtype=jnp.float32)
    dec_fn, _ = dec_build(B, S_max)

    def one_batch(batch_reqs, caches, t0):
        # head-of-line: the batch launches only once EVERY member arrived
        wait = max(r.arrival for r in batch_reqs) - (time.time() - t0)
        if wait > 0:
            time.sleep(wait)
        toks = jnp.asarray(np.stack([r.prompt for r in batch_reqs]),
                           jnp.int32)
        logits, caches = pre_fn(params, caches, {"tokens": toks})
        tok = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
        ids = np.asarray(tok)
        now = time.time() - t0
        for i, r in enumerate(batch_reqs):
            r.generated.append(int(ids[i]))
            r.t_first = now
            if r.max_new == 1:
                r.t_done = now
        gen_max = max(r.max_new for r in batch_reqs)
        tok = tok[:, None]
        for step in range(gen_max - 1):
            logits, caches = dec_fn(params, caches, tok,
                                    jnp.int32(L + step))
            tok = jnp.argmax(logits[:, 0, :], axis=-1).astype(
                jnp.int32)[:, None]
            ids = np.asarray(tok)[:, 0]
            now = time.time() - t0
            for i, r in enumerate(batch_reqs):
                if len(r.generated) < r.max_new:
                    r.generated.append(int(ids[i]))
                    if len(r.generated) == r.max_new:
                        r.t_done = now
        return caches

    # warmup: compile both programs outside the timed window
    warm = ST.zeros_caches(mesh, ct)
    wt = jnp.zeros((B, L), jnp.int32)
    wl, warm = pre_fn(params, warm, {"tokens": wt})
    wl, warm = dec_fn(params, warm,
                      jnp.zeros((B, 1), jnp.int32), jnp.int32(L))
    jax.block_until_ready(wl)
    del warm

    t0 = time.time()
    n_steps = 0
    for k in range(0, len(reqs), B):
        caches = ST.zeros_caches(mesh, ct)
        batch_reqs = reqs[k:k + B]
        caches = one_batch(batch_reqs, caches, t0)
        n_steps += max(r.max_new for r in batch_reqs)
        del caches
    wall = time.time() - t0
    total_new = sum(len(r.generated) for r in reqs)
    l50, l99 = percentiles([(r.t_done - r.arrival) * 1e3 for r in reqs])
    f50, f99 = percentiles([(r.t_first - r.arrival) * 1e3 for r in reqs])
    return ServeStats(n_requests=len(reqs), total_new_tokens=total_new,
                      wall_s=wall, latency_p50_ms=l50, latency_p99_ms=l99,
                      ttft_p50_ms=f50, ttft_p99_ms=f99, n_steps=n_steps,
                      n_preemptions=0)


def run_continuous(cfg, mesh, axes, params, reqs, args, mesh_name=""):
    from repro.launch.serving import PagedEngine, ServeConfig
    scfg = ServeConfig(slots=args.slots, page_size=args.page_size,
                       pages_per_shard=args.pages, chunk=args.chunk)
    engine = PagedEngine(cfg, mesh, axes, params, scfg,
                         dtype=jnp.float32)
    engine.warmup()
    telem = None
    if getattr(args, "telemetry", False):
        from repro.core import comm_model as CM
        from repro.launch import telemetry as TL
        telem = TL.Telemetry(
            f"serving-{mesh_name or 'mesh'}",
            flops_per_token=CM.model_flops_per_token(cfg, "serve"),
            peak_flops_per_device=TL.peak_flops_per_device(),
            n_devices=int(mesh.devices.size), verbose=False,
            meta={"arch": cfg.name, "mesh": mesh_name,
                  "slots": args.slots, "pages": args.pages,
                  "rate": args.rate})
    stats = engine.run(reqs, telemetry=telem)
    if telem is not None:
        # the CSV row and the JSONL summary must quote the SAME number:
        # both take tokens/s from the engine's open-loop wall clock
        telem.close(extra={
            "tok_s": stats.tokens_per_s, "wall_s": stats.wall_s,
            "steps": stats.n_steps, "tokens": stats.total_new_tokens,
            "preemptions": stats.n_preemptions,
            "ttft_p50_ms": stats.ttft_p50_ms,
            "ttft_p99_ms": stats.ttft_p99_ms})
    for alloc in engine.sched.allocators:
        alloc.check()
        assert alloc.n_used == 0, "pages leaked after drain"
    return stats


def _predicted_tokens_per_s(cfg, shape, args, calib: str):
    from repro.core import calibrate as CB
    from repro.core import comm_model as CM
    hw = dataclasses.replace(CB.resolve_hw(calib or None),
                             bytes_per_elem=4.0)
    layers = list(cfg.comm_layers())
    # steady-state decode: batch = slots, context = mean tokens resident
    context = args.prompt_len + (args.gen_min + args.gen_max) / 2.0
    cap = CM.serve_capacity(layers, args.slots,
                            CM.Decomposition(*shape[:4]), hw,
                            context=context)
    return cap.tokens_per_s, cap.step_latency_ms


def suite(calib: str = "", args=None) -> List[Tuple[str, float, str]]:
    """benchmarks.run entry: serve the workload at every mesh that fits
    the host devices, both modes, assert continuous > fixed and token
    parity, report measured + predicted rows and the Spearman rank."""
    from repro.core import calibrate as CB

    if args is None:
        args = build_parser().parse_args([])
    meshes = [(n, s) for n, s in MESHES
              if int(np.prod(s)) == jax.device_count()
              and args.slots % (s[0] * s[3]) == 0]
    if not meshes:
        return [("serving/skipped", 0.0,
                 f"no candidate mesh factors {jax.device_count()} "
                 f"devices")]

    rows, csv_rows = [], []
    measured, predicted = [], []
    for name, shape in meshes:
        cfg, mesh, axes, params = _setup_model(args.arch, shape)
        base = _workload(args, cfg.vocab_size)
        fixed_reqs = _fresh(base)
        cont_reqs = _fresh(base)
        fx = run_fixed_baseline(cfg, mesh, axes, params, fixed_reqs, args)
        ct = run_continuous(cfg, mesh, axes, params, cont_reqs, args,
                            mesh_name=name)

        # paged-vs-dense token parity: greedy ids must agree per request
        for rf, rc in zip(fixed_reqs, cont_reqs):
            assert rf.generated == rc.generated, (
                f"token parity broke at {name} rid={rf.rid}: "
                f"dense={rf.generated} paged={rc.generated}")
        # the tentpole claim: continuous batching strictly beats the
        # head-of-line baseline at the same mesh
        assert ct.tokens_per_s > fx.tokens_per_s, (
            f"continuous ({ct.tokens_per_s:.1f} tok/s) did not beat "
            f"fixed ({fx.tokens_per_s:.1f} tok/s) at {name}")

        pred_tps, pred_ms = _predicted_tokens_per_s(
            cfg, shape, args, calib)
        measured.append(ct.tokens_per_s)
        predicted.append(pred_tps)
        for mode, st in (("fixed", fx), ("continuous", ct)):
            rows.append((f"serving/{name}/{mode}", st.tokens_per_s,
                         f"tok/s lat_p50={st.latency_p50_ms:.1f}ms "
                         f"p99={st.latency_p99_ms:.1f}ms "
                         f"ttft_p50={st.ttft_p50_ms:.1f}ms "
                         f"preempt={st.n_preemptions}"))
            csv_rows.append(
                (name, mode, st.tokens_per_s, st.latency_p50_ms,
                 st.latency_p99_ms, st.ttft_p50_ms, st.ttft_p99_ms,
                 st.n_preemptions,
                 pred_tps if mode == "continuous" else ""))
        rows.append((f"serving/{name}/speedup",
                     ct.tokens_per_s / fx.tokens_per_s,
                     f"continuous/fixed tokens-per-s ratio"))
        rows.append((f"serving/{name}/predicted", pred_tps,
                     f"serve_capacity tok/s step={pred_ms:.3f}ms "
                     f"calib={calib or 'none'}"))

    if len(meshes) >= 2:
        rho = CB.spearman(measured, predicted)
        rows.append(("serving/rank_correlation", rho,
                     f"spearman(measured, predicted) tokens/s over "
                     f"{len(meshes)} meshes calib={calib or 'none'} "
                     f"(host-CPU caveat: per-step dispatch dominates "
                     f"at smoke scale and is unpriced by the model — "
                     f"see EXPERIMENTS.md #serving)"))

    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        f.write("mesh,mode,tokens_per_s,latency_p50_ms,latency_p99_ms,"
                "ttft_p50_ms,ttft_p99_ms,n_preemptions,"
                "predicted_tokens_per_s\n")
        for r in csv_rows:
            f.write(",".join(str(x) for x in r) + "\n")
    rows.append(("serving/csv", float(len(csv_rows)),
                 f"rows written to {args.out}"))
    return rows


def main() -> None:
    # the mesh sweep needs 8 host devices on CPU; set before JAX starts
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=8")
    args = build_parser().parse_args()
    print("name,us_per_call,derived")
    for label, val, derived in suite(calib=args.calib, args=args):
        print(f"{label},{val:.2f},{derived}", flush=True)


if __name__ == "__main__":
    main()
