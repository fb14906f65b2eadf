"""Readings that set a training cell's limits, on the chip at the cell's
size.

    python3 bench/check_control.py --workload <cell> --seeds 12 \
        --control-seeds 3 [--faults half_batch,no_exchange]

For each seed the program's numbers against the reference (the lower
readings); for the first ``--control-seeds`` seeds the control's, the
reference computed in float8 e4m3 in the program's place (the upper
readings); and for each named fault, the program with that fault
planted, on the first ``--control-seeds`` seeds. Each reading is judged
against the cell's committed limits by ``compare.judge``, as a run's is:
a sound run has to come out correct, the control and every fault not.
Prints one JSON line per reading, then the largest program reading and
the smallest control and fault readings of each number, how many readings
of each kind were judged correct, and for each number the limit that
:func:`propose` sets from them. The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import math
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench import common as C  # noqa: E402


def train_readings(cell, devices, seeds, control_seeds, fault=None):
    from bench import compare, faults
    from bench.drivers import train as DT
    k = cell.spec["check_steps"]
    limits = cell.spec["limits"]
    ctx = faults.FAULTS[fault]() if fault else contextlib.nullcontext()
    with ctx:
        tr = DT.Trainer(cell, devices)
    out = []

    def record(seed, kind, numbers):
        ok, _ = compare.judge(numbers, limits)
        out.append({"seed": seed, "kind": kind, **numbers, "correct": ok})
        print(json.dumps(out[-1]), flush=True)

    for i, seed in enumerate(seeds):
        prog = tr.start(seed, k)
        batches = tr.host_batches[:k]
        tr.free()
        ref = DT.reference_readings(cell.config, cell.spec["optimizer"],
                                    seed, batches, devices)
        record(seed, fault or "program", compare.train_numbers(prog, ref))
        if fault is None and i < control_seeds:
            low = DT.reference_readings(cell.config, cell.spec["optimizer"],
                                        seed, batches, devices, mode="fp8")
            record(seed, "control", compare.train_numbers(low, ref))
    return out


def summary(rows):
    nums = sorted({k for r in rows for k in r if k.endswith("_gap")})
    out = {}
    for kind in sorted({r["kind"] for r in rows}):
        sel = [r for r in rows if r["kind"] == kind]
        agg = max if kind == "program" else min
        out[kind] = {k: agg(r[k] for r in sel) for k in nums}
        out[kind]["correct"] = f"{sum(r['correct'] for r in sel)}/{len(sel)}"
    return out


#: A control reading counts as an upper reading from this many times the
#: lower one; a fault reading from ``FAULT_OVER`` times (a state left
#: unchanged reads 1 on ``change_gap`` and needs no run).
CONTROL_OVER, FAULT_OVER = 3.0, 10.0
#: Where between the lower and the upper reading, on a log scale, a limit
#: lies: more of the room above the lower, since fresh seeds read higher.
LIMIT_AT = 0.65


def propose(rows):
    """Per number: the lower reading (the largest of the program's), the
    upper one (the smallest reading of the control, or of a fault, whose
    least reading is over its bar) and the limit between them, to two
    significant digits; no upper and no limit where nothing counts."""
    out = {}
    for k in sorted({k for r in rows for k in r if k.endswith("_gap")}):
        lower = max(r[k] for r in rows if r["kind"] == "program")
        by_kind = {}
        for r in rows:
            if r["kind"] != "program":
                by_kind.setdefault(r["kind"], []).append(r[k])
        ups = [1.0] if k == "change_gap" else []
        ups += [min(v) for kind, v in by_kind.items()
                if min(v) >= (CONTROL_OVER if kind == "control"
                              else FAULT_OVER) * lower]
        upper = min(ups) if ups else None
        limit = None
        if upper is not None:
            lo, hi = math.log10(lower), math.log10(upper)
            limit = float(f"{10 ** (lo + LIMIT_AT * (hi - lo)):.1e}")
        out[k] = {"lower": lower, "upper": upper, "limit": limit}
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2_000_000_011)
    ap.add_argument("--faults", default="")
    args = ap.parse_args(argv)
    cell = C.load_cell(args.workload)
    devices = C.require_devices(cell.chips)
    C.enable_compile_cache()
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    rows = train_readings(cell, devices, seeds, args.control_seeds)
    for f in filter(None, args.faults.split(",")):
        rows += train_readings(cell, devices, seeds[:args.control_seeds], 0,
                               f)
    print(json.dumps({"summary": summary(rows)}), flush=True)
    print(json.dumps({"proposed_limits": propose(rows)}), flush=True)


if __name__ == "__main__":
    main()
