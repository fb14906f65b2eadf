"""Device time of collective ops (all-reduce, all-gather, reduce-scatter,
collective-permute, all-to-all; an async one from its start to its done)
over the traced window, mean over the chips, in percent. Nothing to read
where no collective ran."""


def read(r):
    t = r.get("trace")
    if not t or t["collective_s"] <= 0:
        return None
    return 100.0 * t["collective_s"] / t["window_s"]
