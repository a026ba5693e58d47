"""Faults planted under the timed path, for the tests and for reading
what each fault does to the numbers compared (``calibrate.py``).  The
benchmark's runs never plant one.

* ``unchanged``  every step returns the weights and the optimizer's
  state it was given;
* ``half_batch`` every step sees the first half of its rows only, and
  its loss is the mean over them.
"""
from __future__ import annotations

import contextlib

import jax

NAMES = ("unchanged", "half_batch")


@contextlib.contextmanager
def planted(name: str):
    from repro.core import tuning
    from repro.federated import server
    from repro.optim import cosine_schedule

    orig = server._tune_epoch_fn

    def half_batch(*args):
        epoch = orig(*args)

        def step(params, opt, batch):
            n = batch["tokens"].shape[1] // 2
            return epoch(params, opt, {k: v[:, :n] for k, v in batch.items()})
        return jax.jit(step, donate_argnums=(0, 1))

    def unchanged(moe_cfg, mesh, mask, steps, lr, warmup):
        inner = tuning.make_tune_epoch(
            moe_cfg, mask, steps=steps,
            schedule=cosine_schedule(lr, steps, warmup=warmup), mesh=mesh)

        def step(params, opt, batch):
            return params, opt, inner(params, opt, batch)[2]
        return jax.jit(step)

    server._tune_epoch_fn = {"unchanged": unchanged,
                             "half_batch": half_batch}[name]
    try:
        yield
    finally:
        server._tune_epoch_fn = orig
