"""Serving driver: continuous batching over the paged KV cache (default)
or the legacy fixed-batch prefill/decode loop (``--mode fixed``).

Continuous mode (the production path, docs/serving.md) runs the
``launch/serving`` engine: requests admit/evict at every decode step,
prompts prefill in chunks that ride the same compiled step as decode,
and the KV cache is a paged pool sharded over the tensor axes.

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
  python -m repro.launch.serve --arch qwen3-1.7b --mode continuous \\
      --requests 16 --rate 200 --slots 8 --gen 16 --mesh 2,2,2,1

Fixed mode keeps the PR-0 behavior — one prefill of a uniform batch,
then lockstep decode:

  python -m repro.launch.serve --arch qwen3-1.7b --mode fixed \\
      --batch 4 --prompt-len 32 --gen 16 --mesh 2,2,2,1

The mesh is the 4-tuple g_data,g_x,g_y,g_z — serving requires g_seq == 1
(ring attention is training-only; see ROADMAP 'seq-parallel serving').
"""
from __future__ import annotations

import argparse
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core.overlap import OverlapConfig
from repro.launch import mesh as LM
from repro.launch.compile_cache import enable_compile_cache
from repro.launch import steps as ST


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="python -m repro.launch.serve",
        description="Serving on the current host devices: continuous "
                    "batching over a paged KV cache (default), or the "
                    "fixed-batch prefill/decode loop (--mode fixed).")
    ap.add_argument("--arch", required=True,
                    help="architecture name (repro.configs)")
    ap.add_argument("--preset", default="smoke", choices=["smoke", "full"],
                    help="model-size preset")
    ap.add_argument("--mode", default="continuous",
                    choices=["continuous", "fixed"],
                    help="continuous: paged-KV continuous batching; "
                         "fixed: uniform-batch prefill then lockstep "
                         "decode")
    ap.add_argument("--mesh", default="2,2,2,1",
                    help="g_data,g_x,g_y,g_z over host devices (serving "
                         "needs g_seq == 1)")
    ap.add_argument("--prompt-len", type=int, default=32,
                    help="prompt length in tokens (uniform)")
    ap.add_argument("--gen", type=int, default=16,
                    help="tokens to generate per request/sequence")
    # fixed-mode knobs
    ap.add_argument("--batch", type=int, default=4,
                    help="concurrent sequences (--mode fixed)")
    # continuous-mode knobs
    ap.add_argument("--requests", type=int, default=16,
                    help="synthetic requests to serve (--mode continuous)")
    ap.add_argument("--rate", type=float, default=0.0,
                    help="open-loop Poisson arrival rate in requests/s "
                         "(0 = all requests arrive at t=0)")
    ap.add_argument("--slots", type=int, default=8,
                    help="concurrent request slots R (multiple of "
                         "g_data*g_z)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per KV page")
    ap.add_argument("--pages", type=int, default=64,
                    help="physical KV pages per batch shard (incl. the "
                         "reserved null page)")
    ap.add_argument("--chunk", type=int, default=32,
                    help="prefill chunk rows per mixed step")
    ap.add_argument("--seed", type=int, default=0,
                    help="workload RNG seed")
    ap.add_argument("--telemetry", action="store_true",
                    help="per-step scheduler counters (queue depth, page "
                         "utilization, preemptions) + tokens/s as "
                         "schema'd JSONL under runs/telemetry/ "
                         "(--mode continuous; docs/telemetry.md)")
    ap.add_argument("--log-file", default=None,
                    help="telemetry JSONL path (implies --telemetry; "
                         "default runs/telemetry/<run>.jsonl)")
    ap.add_argument("--overlap", action="store_true",
                    help="ring-decomposed collective matmuls in the "
                         "prefill/decode steps (core/overlap.py: "
                         "overlapped z weight gathers + x/y activation "
                         "all-reduce rings)")
    ap.add_argument("--z-chunks", type=int, default=1,
                    help="sub-rings per z weight block (with --overlap)")
    ap.add_argument("--ar-chunks", type=int, default=1,
                    help="sub-rings per activation all-reduce block "
                         "(with --overlap)")
    return ap


def _setup(args):
    mesh = LM.make_smoke_mesh(tuple(int(x) for x in args.mesh.split(",")),
                              ("data", "x", "y", "z"))
    axes = LM.bind_4d(mesh)
    cfg = get_config(args.arch)
    if args.preset == "smoke":
        cfg = cfg.reduced()
    dtype = jnp.float32
    params, _ = ST.init_sharded(cfg, mesh, axes, jax.random.PRNGKey(0),
                                dtype=dtype)
    ov = (OverlapConfig.all_on(z_chunks=args.z_chunks,
                               ar_chunks=args.ar_chunks)
          if args.overlap else OverlapConfig())
    return cfg, mesh, axes, params, dtype, ov


def run_fixed(args) -> None:
    cfg, mesh, axes, params, dtype, ov = _setup(args)
    S_max = args.prompt_len + args.gen
    pre_build, _ = ST.make_prefill_step(cfg, mesh, axes, dtype=dtype,
                                        overlap=ov)
    pre_fn, bt, ct = pre_build(args.batch, args.prompt_len, S_max)
    dec_build, _ = ST.make_decode_step(cfg, mesh, axes, dtype=dtype,
                                       overlap=ov)
    dec_fn, _ = dec_build(args.batch, S_max)

    rng = np.random.RandomState(args.seed)
    batch = {"tokens": jnp.asarray(
        rng.randint(0, cfg.vocab_size, (args.batch, args.prompt_len)),
        jnp.int32)}
    if cfg.arch_type == "vlm":
        batch["image_embeds"] = jnp.asarray(rng.randn(
            args.batch, cfg.encoder.n_ctx, cfg.encoder.input_dim),
            jnp.float32)
    if cfg.arch_type == "audio":
        batch["frames"] = jnp.asarray(rng.randn(
            args.batch, cfg.encoder.n_ctx, cfg.d_model), jnp.float32)

    # warmup: run prefill + one decode step on throwaway caches so the
    # timed numbers below exclude XLA compile time
    warm = ST.zeros_caches(mesh, ct)
    t0 = time.time()
    wl, warm = pre_fn(params, warm, batch)
    wt = jnp.argmax(wl[:, -1, :], axis=-1)[:, None].astype(jnp.int32)
    wl, warm = dec_fn(params, warm, wt, jnp.int32(args.prompt_len))
    jax.block_until_ready(wl)
    print(f"warmup (compile) in {time.time()-t0:.2f}s")
    del warm

    caches = ST.zeros_caches(mesh, ct)
    t0 = time.time()
    logits, caches = pre_fn(params, caches, batch)
    tok = jnp.argmax(logits[:, -1, :], axis=-1)[:, None].astype(jnp.int32)
    print(f"prefill {args.batch}x{args.prompt_len} in "
          f"{time.time()-t0:.2f}s")

    out = [np.asarray(tok)]
    t0 = time.time()
    for i in range(args.gen - 1):
        pos = jnp.int32(args.prompt_len + i)
        logits, caches = dec_fn(params, caches, tok, pos)
        tok = jnp.argmax(logits[:, 0, :], axis=-1)[:, None].astype(jnp.int32)
        out.append(np.asarray(tok))
    dt = time.time() - t0
    gen = np.concatenate(out, axis=1)
    print("generated ids:\n", gen)
    print(f"decode: {args.gen - 1} steps x batch {args.batch} = "
          f"{(args.gen - 1) * args.batch / dt:,.1f} tok/s")
    assert np.isfinite(np.asarray(logits)).all()
    print("SERVE OK")


def run_continuous(args) -> None:
    from repro.launch.serving import PagedEngine, Request, ServeConfig

    cfg, mesh, axes, params, dtype, ov = _setup(args)
    scfg = ServeConfig(slots=args.slots, page_size=args.page_size,
                       pages_per_shard=args.pages, chunk=args.chunk)
    engine = PagedEngine(cfg, mesh, axes, params, scfg, dtype=dtype,
                         overlap=ov)
    t0 = time.time()
    engine.warmup()
    print(f"warmup (compile) in {time.time()-t0:.2f}s")

    rng = np.random.RandomState(args.seed)
    t = 0.0
    reqs = []
    for i in range(args.requests):
        if args.rate > 0:
            t += float(rng.exponential(1.0 / args.rate))
        reqs.append(Request(
            rid=i,
            prompt=rng.randint(1, cfg.vocab_size,
                               size=(args.prompt_len,)).astype(np.int32),
            max_new=args.gen, arrival=t))
    telem = None
    if args.telemetry or args.log_file:
        from repro.core import comm_model as CM
        from repro.launch import telemetry as TL
        run_name = f"serve-{cfg.name}-{time.strftime('%Y%m%d-%H%M%S')}"
        telem = TL.Telemetry(
            run_name, path=args.log_file,
            tokens_per_step=0,  # serve steps carry their own new_tokens
            flops_per_token=CM.model_flops_per_token(cfg, "serve"),
            peak_flops_per_device=TL.peak_flops_per_device(),
            n_devices=int(mesh.devices.size),
            meta={"arch": cfg.name, "mesh": args.mesh, "mode": "continuous",
                  "slots": args.slots, "pages": args.pages,
                  "requests": args.requests, "rate": args.rate})
    stats = engine.run(reqs, telemetry=telem)
    if telem is not None:
        # summary tok_s comes from the engine's open-loop wall clock so
        # the JSONL agrees with the printed stats (and the perf CSV)
        telem.close(extra={
            "tok_s": stats.tokens_per_s, "wall_s": stats.wall_s,
            "steps": stats.n_steps, "tokens": stats.total_new_tokens,
            "preemptions": stats.n_preemptions,
            "ttft_p50_ms": stats.ttft_p50_ms,
            "ttft_p99_ms": stats.ttft_p99_ms,
            "latency_p50_ms": stats.latency_p50_ms,
            "latency_p99_ms": stats.latency_p99_ms})
    for r in reqs[: min(4, len(reqs))]:
        print(f"req {r.rid}: {np.asarray(r.generated, np.int32)}")
    print(f"served {stats.n_requests} requests / "
          f"{stats.total_new_tokens} tokens in {stats.wall_s:.2f}s "
          f"({stats.n_steps} steps, {stats.n_preemptions} preemptions)")
    print(f"tokens/s {stats.tokens_per_s:,.1f}  "
          f"latency p50/p99 {stats.latency_p50_ms:.1f}/"
          f"{stats.latency_p99_ms:.1f} ms  "
          f"ttft p50/p99 {stats.ttft_p50_ms:.1f}/"
          f"{stats.ttft_p99_ms:.1f} ms")
    print("SERVE OK")


def main():
    args = build_parser().parse_args()
    enable_compile_cache()
    if args.mode == "fixed":
        run_fixed(args)
    else:
        run_continuous(args)


if __name__ == "__main__":
    main()
