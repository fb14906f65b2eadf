"""Share of the attention core's device time that runs in Pallas kernels,
in percent: the seconds of the ``attn_core`` scope's custom calls (a
Pallas kernel lowers to one) over all ``attn_core`` seconds in the
traced window, every chip's ops summed. Collectives are left out. Nothing
to read without layer names in the executable, or without attention core
time in the window."""
from bench import scopes


def read(r):
    t = r.get("trace")
    smap = scopes.run_map() if t else None
    if not smap:
        return None
    kernel = total = 0.0
    for label, s in t["ops"].items():
        name = label.split(" ", 1)[0]
        opcode, layer = smap.get(name, ("", None))
        if layer != "attn_core" or scopes.is_collective(name, opcode):
            continue
        total += s
        if opcode == "custom-call":
            kernel += s
    return 100.0 * kernel / total if total else None
