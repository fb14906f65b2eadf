"""Smoke run of the training and paged-serving paths on TPU chips.

    python chip_smoke.py             # one chip: train, then serve
    python chip_smoke.py --chips 4   # four chips: full-depth training

One chip: qwen3-1.7b at its published widths, cut to 4 of its 28 layers,
takes a few AdamW steps on one fixed batch through the library calls that
``launch/train.py`` makes. The trained weights then serve a few seeded
requests through the paged continuous-batching engine, and their greedy
tokens must equal those of the dense fixed-batch path.

Four chips (``--chips 4``): the full 28-layer model, which one chip
cannot train, takes a few steps under two 4D decompositions of a v5e:2x2
host. Their step-0 losses must agree, and every chip must hold only its
share of the training state.

Weights are random, made from ``--seed``. The times printed are smoke
timings of a few steps, not benchmarks. The last line of standard output
is one JSON object naming the device; it is printed only when every check
passed. Without a TPU the script exits non-zero before it runs anything.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import sys
import time
import types
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import numpy as np  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.data.synthetic import DataConfig, SyntheticText, \
    make_batch  # noqa: E402
from repro.launch import mesh as LM  # noqa: E402
from repro.launch import steps as ST  # noqa: E402
from repro.launch import telemetry as TL  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.optim import adamw as OPT  # noqa: E402

ARCH = "qwen3-1.7b"
SMOKE_LAYERS = 4                  # the one-chip depth cut
BATCH, SEQ, OVERDECOMPOSE = 4, 1024, 2
LR = 3e-4
TRAIN_STEPS = 5
FOUR_CHIP_STEPS = 3
DECOMPOSITIONS = ((1, 2, 2, 1), (1, 1, 2, 2))   # (g_data, g_x, g_y, g_z)
# Two decompositions sum the same bf16 products in another order. Two
# bf16 ulps of the loss (2**-7 relative) bounds that; a sharding error
# moves the loss by far more once the weights have been updated.
LOSS_RTOL = 2.0 ** -7
# Serving workload: two prompts of each length, 16-32 new tokens each.
PROMPT_LENGTHS = (64, 128, 256, 512)
GEN_MIN, GEN_MAX = 16, 32
SERVE = dict(slots=8, page_size=16, pages_per_shard=512, chunk=64)


def require_tpu(n_chips: int) -> list:
    """The first ``n_chips`` TPU devices; raises unless JAX runs on a TPU
    with at least that many. Nothing here falls back to another device."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise RuntimeError(
            f"chip_smoke needs a TPU; JAX found {devices[0].platform} "
            f"({devices[0].device_kind})")
    if len(devices) < n_chips:
        raise RuntimeError(f"chip_smoke --chips {n_chips} needs {n_chips} "
                           f"TPU chips; JAX found {len(devices)}")
    return devices[:n_chips]


def _tree_bytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def train(cfg, factors, seed: int, steps: int):
    """Build the mesh, the model and the AdamW state as ``launch/train.py``
    does, then take ``steps`` steps on one fixed batch.

    Returns ``(params, state, info)``; ``info`` holds the losses, the
    compile time, the warm step times and the compiled step's memory
    analysis."""
    mesh, axes = LM.MeshLifecycle(*factors).build()
    dtype = jnp.bfloat16
    params, _ = ST.init_sharded(cfg, mesh, axes, jax.random.PRNGKey(seed),
                                dtype=dtype)
    state = OPT.init_state(params)
    topts = ST.TrainOptions(overdecompose=OVERDECOMPOSE, dtype=dtype)
    opt = OPT.AdamWConfig(lr=LR, warmup_steps=1, total_steps=steps)
    step_fn, _, _ = ST.make_train_step(cfg, mesh, axes, opt, topts)
    data = SyntheticText(DataConfig(vocab_size=cfg.vocab_size, seq_len=SEQ,
                                    global_batch=BATCH, seed=seed))
    batch = {k: jnp.asarray(v) for k, v in make_batch(cfg, 0, data).items()}

    t0 = time.perf_counter()
    compiled = step_fn.lower(params, state, batch).compile()
    compile_s = time.perf_counter() - t0
    losses, step_s = [], []
    for i in range(steps):
        t0 = time.perf_counter()
        params, state, metrics = compiled(params, state, batch)
        jax.block_until_ready((params, state, metrics))
        step_s.append(time.perf_counter() - t0)
        losses.append(float(metrics["loss"]))
        print(f"  step {i} loss {losses[-1]:.6f} "
              f"grad_norm {float(metrics['grad_norm']):.4f}", flush=True)
    info = dict(mesh=mesh, axes=axes, losses=losses, compile_s=compile_s,
                step_s=step_s, memory=compiled.memory_analysis())
    return params, state, info


def _print_timings(tag: str, info: dict) -> None:
    warm = info["step_s"][1:]
    print(f"{tag}: compile {info['compile_s']:.3f} s; warm step "
          f"{float(np.median(warm)):.6f} s (median of {len(warm)}; smoke "
          f"timing, not a benchmark)", flush=True)


def _check_losses(tag: str, losses) -> None:
    if not all(math.isfinite(x) for x in losses):
        raise RuntimeError(f"{tag}: non-finite loss in {losses}")
    if not losses[-1] < losses[0]:
        raise RuntimeError(f"{tag}: loss did not decrease: {losses}")


def serve(cfg, mesh, axes, params, seed: int) -> None:
    """Serve seeded requests through ``PagedEngine`` as ``launch/serve.py``
    builds it, and the same prompts through the dense fixed-batch path of
    ``benchmarks/serving.py``; the greedy tokens must be equal.

    Both run in fp32 at the highest matmul precision, as the serving CLI
    runs fp32: the two paths reduce attention over different lengths, and
    at bf16 matmul precision such rounding can flip a near-tied argmax of
    random weights."""
    from benchmarks.serving import run_fixed_baseline
    from repro.launch.serving import PagedEngine, Request, ServeConfig

    rng = np.random.RandomState(seed)
    lengths = rng.permutation(np.repeat(PROMPT_LENGTHS, 2))
    prompts = [rng.randint(1, cfg.vocab_size, size=(int(n),)
                           ).astype(np.int32) for n in lengths]
    max_new = rng.randint(GEN_MIN, GEN_MAX + 1, size=len(prompts))

    def requests():
        return [Request(rid=i, prompt=p, max_new=int(n))
                for i, (p, n) in enumerate(zip(prompts, max_new))]

    params = jax.tree.map(lambda p: p.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        engine = PagedEngine(cfg, mesh, axes, params, ServeConfig(**SERVE),
                             dtype=jnp.float32)
        t0 = time.perf_counter()
        engine.warmup()
        print(f"serve: paged steps compiled in "
              f"{time.perf_counter() - t0:.3f} s", flush=True)
        paged = requests()
        stats = engine.run(paged)
        dense = requests()
        for n in PROMPT_LENGTHS:
            group = [r for r in dense if len(r.prompt) == n]
            run_fixed_baseline(cfg, mesh, axes, params, group,
                               types.SimpleNamespace(slots=len(group),
                                                     prompt_len=n))
    print(f"serve: {stats.n_requests} requests, {stats.total_new_tokens} "
          f"tokens in {stats.n_steps} steps, {stats.wall_s:.3f} s (smoke "
          f"timing, not a benchmark)", flush=True)
    for rp, rd in zip(paged, dense):
        print(f"  req {rp.rid}: prompt {len(rp.prompt)} new {rp.max_new} "
              f"state {rp.state} tokens {rp.generated[:6]}...", flush=True)
        if rp.state != "done" or len(rp.generated) != rp.max_new:
            raise RuntimeError(f"serve: request {rp.rid} did not complete "
                               f"({rp.state}, {len(rp.generated)} of "
                               f"{rp.max_new} tokens)")
        if rp.generated != rd.generated:
            raise RuntimeError(f"serve: request {rp.rid}: paged tokens "
                               f"{rp.generated} != dense {rd.generated}")
    print(f"serve: all {len(paged)} requests completed; greedy tokens "
          f"equal the dense path's", flush=True)


def one_chip(seed: int) -> None:
    full = get_config(ARCH)
    cfg = dataclasses.replace(full, n_layers=SMOKE_LAYERS)
    print(f"reduced: n_layers {full.n_layers}->{cfg.n_layers}", flush=True)
    print(f"train: {ARCH} d_model {cfg.d_model} heads {cfg.n_heads}/"
          f"{cfg.n_kv_heads}x{cfg.head_dim} d_ff {cfg.d_ff} vocab "
          f"{cfg.vocab_size}, mesh (1,1,1,1), bf16, batch {BATCH}x{SEQ}, "
          f"overdecompose {OVERDECOMPOSE}", flush=True)
    params, state, info = train(cfg, (1, 1, 1, 1), seed, TRAIN_STEPS)
    _print_timings("train", info)
    _check_losses("train", info["losses"])
    peak = TL.peak_memory_bytes()
    if peak is None:
        raise RuntimeError("train: the TPU reported no peak_bytes_in_use")
    print(f"train: peak_bytes_in_use {peak}", flush=True)
    del state
    serve(cfg, info["mesh"], info["axes"], params, seed)


def four_chips(devices, seed: int) -> None:
    cfg = get_config(ARCH)
    print(f"train: {ARCH} at full depth ({cfg.n_layers} layers), bf16, "
          f"batch {BATCH}x{SEQ}, overdecompose {OVERDECOMPOSE}", flush=True)
    losses = {}
    for factors in DECOMPOSITIONS:
        print(f"train: mesh (g_data,g_x,g_y,g_z) = {factors}", flush=True)
        params, state, info = train(cfg, factors, seed, FOUR_CHIP_STEPS)
        _print_timings(f"train {factors}", info)
        losses[factors] = info["losses"]
        _check_losses(f"train {factors}", info["losses"])
        held = {s.device for p in jax.tree.leaves(params)
                for s in p.addressable_shards}
        if len(held) != len(devices):
            raise RuntimeError(f"train {factors}: parameter shards sit on "
                               f"{len(held)} devices, not {len(devices)}")
        total = _tree_bytes(params) + _tree_bytes(state)
        per_dev = {d.id: 0 for d in devices}
        for leaf in jax.tree.leaves((params, state)):
            for s in leaf.addressable_shards:
                per_dev[s.device.id] += s.data.nbytes
        ma = info["memory"]
        print(f"train {factors}: state {total} bytes in all; per device "
              f"{per_dev}; compiled step arguments "
              f"{ma.argument_size_in_bytes} + temporaries "
              f"{ma.temp_size_in_bytes} bytes per device", flush=True)
        for d, b in per_dev.items():
            if not 0.2 * total <= b <= total / 3:
                raise RuntimeError(f"train {factors}: device {d} holds "
                                   f"{b} of {total} state bytes, not "
                                   f"about a quarter")
        del params, state
    peak = {d.id: d.memory_stats()["peak_bytes_in_use"] for d in devices}
    print(f"train: peak_bytes_in_use per device {peak}", flush=True)
    for d, b in peak.items():
        if b >= 0.6 * total:
            raise RuntimeError(f"device {d}: peak_bytes_in_use {b} is not "
                               f"well below the whole state ({total})")
    a, b = (losses[f][0] for f in DECOMPOSITIONS)
    print(f"train: step-0 losses {a:.6f} vs {b:.6f}, |diff| "
          f"{abs(a - b):.6f}, tolerance {LOSS_RTOL * abs(a):.6f} "
          f"(rtol 2**-7)", flush=True)
    if not abs(a - b) <= LOSS_RTOL * abs(a):
        raise RuntimeError(f"step-0 losses disagree across "
                           f"decompositions: {a} vs {b}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: train + serve at 4 layers on one chip; 4: "
                         "full-depth training under two decompositions")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the weights, the batch and the requests")
    args = ap.parse_args(argv)
    cache = enable_compile_cache()
    devices = require_tpu(args.chips)
    print(f"device: {devices[0].platform} {devices[0].device_kind} "
          f"x{len(devices)}; jax {jax.__version__}; compile cache {cache}",
          flush=True)
    if args.chips == 4:
        four_chips(devices, args.seed)
    else:
        one_chip(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
