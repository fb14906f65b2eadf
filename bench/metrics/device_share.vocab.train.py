"""Device time of the vocabulary's work (the ``vocab`` scope of
repro.core.trace: the embedding gather and its scatter-add gradient, the
logits and the vocab-parallel cross-entropy), over the traced window,
mean over the chips, in percent. Collectives are left out. Nothing to
read without layer names in the executable."""
from bench import scopes


def read(r):
    return scopes.share(r, "vocab")
