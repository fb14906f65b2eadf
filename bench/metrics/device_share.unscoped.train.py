"""Device time of compute ops that carry no layer name of
repro.core.trace (loop plumbing, layout copies, prefetches), over the
traced window, mean over the chips, in percent: the coverage guard of
the other device_share readers. The longest such ops go to standard
error. Collectives are left out. Nothing to read without layer names in
the executable."""
from bench import scopes


def read(r):
    scopes.log_unscoped(r)
    return scopes.share(r, scopes.UNSCOPED)
