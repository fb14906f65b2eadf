"""Device time of the attention block outside its softmax core (the ``attn``
scope of repro.core.trace less the ``attn_core`` inside it: q/k/v/o
projections, rotary, layout changes, the residual add), over the traced
window, mean over the chips, in percent. Collectives are left out.
Nothing to read without layer names in the executable."""
from bench import scopes


def read(r):
    return scopes.share(r, "attn")
