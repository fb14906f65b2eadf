"""Faults planted underneath the timed path, for the checks that show
``correct`` comes out false: each is a context manager that patches the
program while its steps are traced and compiled.

* ``state_unchanged``: the train step returns its parameters and AdamW
  state as it got them.
* ``half_batch``: the loss leaves out half of the batch's rows and takes
  the mean over the rest.
* ``no_exchange``: every all-reduce between chips is left out.
"""
from __future__ import annotations

import contextlib


@contextlib.contextmanager
def _patched(module, name, fn):
    old = getattr(module, name)
    setattr(module, name, fn)
    try:
        yield
    finally:
        setattr(module, name, old)


def state_unchanged():
    from repro.optim import adamw as OPT

    def apply_updates(params, grads, state, specs, axes, cfg):
        import jax.numpy as jnp
        z = jnp.zeros((), jnp.float32)
        return params, state, {"grad_norm": z, "lr": z}
    return _patched(OPT, "apply_updates", apply_updates)


def half_batch():
    from repro.models import decoder as D
    full = D.lm_loss

    def lm_loss(params, cfg, axes, tokens, labels, **kw):
        h = tokens.shape[0] // 2
        return full(params, cfg, axes, tokens[:h], labels[:h], **kw)
    return _patched(D, "lm_loss", lm_loss)


def no_exchange():
    from repro.core import mesh as M
    return _patched(M, "psum", lambda v, axis: v)


FAULTS = {"state_unchanged": state_unchanged, "half_batch": half_batch,
          "no_exchange": no_exchange}
