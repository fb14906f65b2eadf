"""Device time of the dense MLP and its residual add (the ``mlp`` scope of
repro.core.trace), over the traced window, mean over the chips, in
percent. Collectives are left out. Nothing to read without layer names
in the executable."""
from bench import scopes


def read(r):
    return scopes.share(r, "mlp")
