"""Device time of the mixture-of-experts layers outside the routed experts'
products (the ``moe`` scope of repro.core.trace: router, sort and
dispatch, combine, shared experts), over the traced window, mean over
the chips, in percent. Collectives are left out. Nothing to read without
layer names in the executable."""
from bench import scopes


def read(r):
    return scopes.share(r, "moe")
