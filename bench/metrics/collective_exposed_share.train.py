"""The part of the collectives' device time during which no other op ran
on the same chip, over the traced window, mean over the chips, in
percent. Nothing to read where no collective ran."""


def read(r):
    t = r.get("trace")
    if not t or t["collective_s"] <= 0:
        return None
    return 100.0 * t["collective_exposed_s"] / t["window_s"]
