"""Run one benchmark cell once on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell is an entry of ``BENCHMARK.json``'s workloads; its files are
``bench/workloads/<cell>.json`` (driver, mesh, options, limits), the
configuration and traffic files it names, and one reader per per-layer
metric in ``bench/metrics/``. Set-up (compiling or loading every program
the window uses, making the weights, the first steps) is timed from
process start as ``setup_s``; then the window runs for ``--seconds``.
With ``--trace 0`` the result holds the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window. After the window the plain reference checks what the timed path
produced, and the last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and
``breakdown`` with ``--trace 1``), then ``checks``, the compared numbers
beside their limits. Without an accelerator, or with fewer chips than
the cell asks for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench import common as C  # noqa: E402


def per_layer(cell, ctx: dict) -> dict:
    out = {}
    for m in cell.per_layer:
        v = C.load_reader(m["name"])(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def end_to_end(cell, values: dict) -> dict:
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end}


def run_train(cell, seed, seconds, trace, devices):
    from bench import compare, flops
    from bench import trace_reduce as TRR
    from bench.drivers import train as DT
    k = cell.spec["check_steps"]
    tr = DT.Trainer(cell, devices)
    prog = tr.start(seed, k)
    spans = C.Spans()
    with TRR.Capture(trace, spans, C.RUN_DIR / "trace") as cap:
        setup_s = time.perf_counter() - T_START
        with spans("bench.window"):
            w = tr.window(seconds, spans)
    device = C.device_info(devices)
    host_batches = tr.host_batches[:k]
    tr.free()
    del tr
    chips = len(devices)
    values = {"setup_s": setup_s,
              "train_tokens_per_s": w["tokens"] / w["window_s"] / chips}
    result = {"attempted": w["steps"], "failed": w["nonfinite"]}
    if trace:
        red = cap.read("bench.window")
        S = cell.traffic["seq_len"]
        ctx = {"trace": red, "window_s": w["window_s"], "chips": chips,
               "tokens": w["tokens"],
               "flops_per_token": flops.train_flops_per_token(cell.config,
                                                              S),
               "peak": flops.peak(device["kind"])}
        result["metrics"] = per_layer(cell, ctx)
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        result["breakdown"] = TRR.breakdown(red)
    else:
        result["metrics"] = end_to_end(cell, values)
    result["device"] = device
    C.log(f"window: {w}; setup_s {setup_s:.3f}; {device}")
    t_ref = time.perf_counter()
    ref = DT.reference_readings(cell.config, cell.spec["optimizer"], seed,
                                host_batches, devices)
    C.log(f"reference: {time.perf_counter() - t_ref:.3f} s")
    numbers = compare.train_numbers(prog, ref)
    ok, checks = compare.judge(numbers, cell.spec["limits"])
    result["correct"] = bool(ok and w["nonfinite"] == 0)
    return result, checks


DRIVERS = {"train": run_train}


def execute(cell, seed: int, seconds: float, trace: bool, devices):
    result, checks = DRIVERS[cell.spec["driver"]](cell, seed, seconds,
                                                  trace, devices)
    order = ["correct", "attempted", "failed", "metrics", "device"]
    out = {k: result[k] for k in order}
    if "breakdown" in result:
        out["breakdown"] = result["breakdown"]
    return out, checks


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = C.load_cell(args.workload)
    devices = C.require_devices(cell.chips)
    C.enable_compile_cache()
    result, checks = execute(cell, args.seed, args.seconds,
                             bool(args.trace), devices)
    C.emit(result, checks)


if __name__ == "__main__":
    main()
