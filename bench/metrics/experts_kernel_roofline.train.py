"""Roofline share of the routed experts' grouped-product kernel, in
percent: the least time the chip could take for the products' work in
the traced window, the larger of its FLOPs over the bf16 peak and its
bytes over the HBM bandwidth (bench/peaks.json), over the seconds of
the kernel's calls (custom calls in the ``experts`` scope), every chip's
summed. The work is the family's ``experts_kernel_work`` at the cell's
expected held load (tokens x top-k x held / routed experts rows): the
up and down products forward, recomputed and twice backward. Nothing to
read without layer names in the executable, without a family that
counts that work, or without such calls in the window."""
import argparse

from bench import common as C
from bench import scopes


def read(r):
    t = r.get("trace")
    smap = scopes.run_map() if t else None
    if not smap:
        return None
    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--workload", required=True)
    cell = C.load_cell(ap.parse_known_args()[0].workload)
    work = getattr(C.family(cell.config), "experts_kernel_work", None)
    seconds = 0.0
    for label, s in t["ops"].items():
        name = label.split(" ", 1)[0]
        opcode, layer = smap.get(name, ("", None))
        if layer == "experts" and opcode == "custom-call":
            seconds += s
    if work is None or not seconds:
        return None
    flops, bytes_ = work(cell.config, r["tokens"])
    least = max(flops / r["peak"]["bf16_flops_per_s"],
                bytes_ / r["peak"]["hbm_bytes_per_s"])
    return 100.0 * least / seconds
