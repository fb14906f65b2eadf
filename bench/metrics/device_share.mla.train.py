"""Device time of the latent attention's projections, latent norm and YaRN
rotary, outside its softmax core (the ``mla`` scope of
repro.core.trace), over the traced window, mean over the chips, in
percent. Collectives are left out. Nothing to read without layer names
in the executable."""
from bench import scopes


def read(r):
    return scopes.share(r, "mla")
