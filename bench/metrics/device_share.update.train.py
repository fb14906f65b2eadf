"""Device time of the update (the ``update`` scope of repro.core.trace:
gradient accumulation over microbatches, reductions, norm and clip,
AdamW), over the traced window, mean over the chips, in percent.
Collectives are left out. Nothing to read without layer names in the
executable."""
from bench import scopes


def read(r):
    return scopes.share(r, "update")
