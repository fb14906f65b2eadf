"""Device time of the attention's softmax core (the ``attn_core`` scope of
repro.core.trace: scores, softmax, PV), over the traced window, mean
over the chips, in percent. Collectives are left out. Nothing to read
without layer names in the executable."""
from bench import scopes


def read(r):
    return scopes.share(r, "attn_core")
