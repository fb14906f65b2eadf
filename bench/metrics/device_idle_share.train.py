"""Share of the traced training window in which no op ran on a chip,
mean over the cell's chips, in percent."""


def read(r):
    t = r.get("trace")
    if not t:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
