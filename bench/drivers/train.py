"""Training driver: the program's compiled train step
(``launch/steps.make_train_step``) driven from the seed's weights.

Set-up compiles the step, makes the weights and the AdamW state in one
jitted call, places the seed's batches on the chips, and takes the first
steps through the same compiled call that the window drives, reading what
the comparison with the reference needs. The window then runs that same
object for ``--seconds``: one step in flight behind the one dispatched.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from bench import program as PG
from bench import reference as REF
from bench import traffic as TR
from bench import weights as W
from bench.common import base_key, family


class Trainer:
    """One compiled step for a cell; ``start(seed)`` gives it state."""

    def __init__(self, cell, devices):
        from repro.launch import steps as ST
        from repro.optim import adamw as OPT
        self.cell, self.c = cell, cell.config
        self.spec, self.traffic = cell.spec, cell.traffic
        self.arch = PG.arch(self.c)
        self.mesh, self.axes = PG.mesh(self.spec["mesh"], devices)
        self.dtype = PG.dtype(self.c)
        self.opt = dict(self.spec["optimizer"])
        topts = ST.TrainOptions(dtype=self.dtype,
                                **self.spec.get("train_options", {}))
        step_fn, pspecs, sspecs = ST.make_train_step(
            self.arch, self.mesh, self.axes, OPT.AdamWConfig(**self.opt),
            topts)
        structs, _ = PG.param_layout(self.c, self.axes)
        pshard = PG.named(self.mesh, pspecs)
        sstructs = OPT.init_state(structs, abstract=True)
        sshard = PG.named(self.mesh, sspecs)
        B, S = self.traffic["global_batch"], self.traffic["seq_len"]
        bt = ST.batch_struct(self.arch, self.axes, B, S)
        self.bshard = {k: NamedSharding(self.mesh, v[1])
                       for k, v in bt.items()}
        c = self.c
        to_program = family(c).to_program

        def make(key):
            p = to_program(W.canonical(c, key), c)
            return p, OPT.init_state(p)
        self._make = jax.jit(make, out_shardings=(pshard, sshard))
        names, treedef = jax.tree.flatten(W.program_names(c))

        def opt_leaves(state, field):
            sub = treedef.flatten_up_to(state["opt"])
            return {n: s[field] for n, s in zip(names, sub)}
        b1 = self.opt["b1"]
        self._grad_norms = jax.jit(lambda s: {
            k: v / (1 - b1) for k, v in
            REF.slice_norms(opt_leaves(s, "m")).items()})
        self._change_norms = jax.jit(lambda s, key: REF.change_norms(
            opt_leaves(s, "master"), W.canonical(c, key)))
        abstract = (jax.tree.map(
            lambda x, sh: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                               sharding=sh),
            structs, pshard),
            jax.tree.map(lambda x, sh: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=sh), sstructs, sshard),
            {k: jax.ShapeDtypeStruct((B, S), jnp.int32, sharding=sh)
             for k, sh in self.bshard.items()})
        self.step = step_fn.lower(*abstract).compile()
        self.tokens_per_step = B * S

    def start(self, seed: int, check_steps: int):
        """Weights, state and the feed of ``seed``; then the first
        ``check_steps`` steps. Returns the program's readings."""
        self.seed = seed
        key = base_key(seed)
        self.params, self.state = self._make(key)
        n_feed = max(self.traffic["feed_batches"], check_steps)
        self.host_batches = TR.train_batches(
            self.traffic, self.c["vocab_size"], seed, n_feed)
        self.feed = [{"tokens": jax.device_put(t, self.bshard["tokens"]),
                      "labels": jax.device_put(l, self.bshard["labels"])}
                     for t, l in self.host_batches]
        losses, grad = [], None
        for i in range(check_steps):
            self.params, self.state, m = self.step(self.params, self.state,
                                                   self.feed[i])
            losses.append(float(m["loss"]))
            if i == 0:
                grad = jax.device_get(self._grad_norms(self.state))
        change = jax.device_get(self._change_norms(self.state, key))
        self.next = check_steps
        return {"loss": np.asarray(losses),
                "grad": {k: np.asarray(v) for k, v in grad.items()},
                "change": {k: np.asarray(v) for k, v in change.items()}}

    def window(self, seconds: float, spans) -> dict:
        """Steps until ``seconds`` have passed; the last one included."""
        n, losses, prev, steps = len(self.feed), [], None, 0
        p, s = self.params, self.state
        t0 = time.perf_counter()
        while True:
            with spans("train.dispatch"):
                p, s, m = self.step(p, s, self.feed[self.next % n])
            self.next += 1
            steps += 1
            if prev is not None:
                with spans("train.sync"):
                    losses.append(float(prev["loss"]))
            prev = m
            if time.perf_counter() - t0 >= seconds:
                break
        with spans("train.sync"):
            losses.append(float(prev["loss"]))
            jax.block_until_ready((p, s))
        t1 = time.perf_counter()
        self.params, self.state = p, s
        return {"steps": steps, "window_s": t1 - t0,
                "tokens": steps * self.tokens_per_step,
                "nonfinite": int(np.sum(~np.isfinite(losses)))}

    def free(self):
        self.params = self.state = self.feed = None


def reference_shardings(c: dict, devices):
    """Placement of the reference's float32 weights over the cell's
    chips, by the family's ``reference_spec`` on a mesh of one axis
    ``"tp"``, so that weights, AdamW's moments and gradients fit beside
    each other."""
    from jax.sharding import Mesh
    mesh = Mesh(np.asarray(devices), ("tp",))
    fam = family(c)
    return {name: NamedSharding(mesh, fam.reference_spec(name, shape))
            for name, shape in fam.canonical_shapes(c).items()}


def reference_readings(c, opt, seed, host_batches, devices,
                       mode="fp32") -> dict:
    """The reference's readings over the same batches, on the same
    chips, placed by :func:`reference_shardings`."""
    rep = NamedSharding(jax.sharding.Mesh(np.asarray(devices), ("tp",)),
                        jax.sharding.PartitionSpec())
    batches = [(jax.device_put(t, rep), jax.device_put(l, rep))
               for t, l in host_batches]
    return REF.train_readings(c, opt, seed, batches, mode=mode,
                              shardings=reference_shardings(c, devices))
