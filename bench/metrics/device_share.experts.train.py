"""Device time of the routed experts' grouped products (the ``experts``
scope of repro.core.trace, inside ``moe``), over the traced window, mean
over the chips, in percent. Collectives are left out. Nothing to read
without layer names in the executable."""
from bench import scopes


def read(r):
    return scopes.share(r, "experts")
