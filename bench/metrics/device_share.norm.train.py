"""Device time of every LayerNorm and RMSNorm (the ``norm`` scope of
repro.core.trace, final norm included), over the traced window, mean
over the chips, in percent. Collectives are left out. Nothing to read
without layer names in the executable."""
from bench import scopes


def read(r):
    return scopes.share(r, "norm")
