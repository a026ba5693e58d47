"""Phase III (DeepFusion §IV.D) of a DeepSeek-V3-architecture MoE:
multi-head latent attention, leading dense layers, and experts under the
sigmoid router with its correction bias, tuned with the expert FFNs and
the bias frozen, through the jitted epoch the federated server builds
(``federated.server._tune_epoch_fn`` over ``core.tuning``).

It runs as ``jobs/tune.py`` does (the same window, ``IN_FLIGHT`` epochs
of one step queued, the same readings of the first steps for
``correct``), with the weights laid out and made as the MLA reference
(``reference/mla_moe_lm.py``) makes them and that reference deciding
``correct``.

The window's dict carries ``expert_load``: a function that counts,
under the window's final weights, how many of the window's last batch's
assignments each expert of each expert layer receives.  Its reader calls
it after the window, so that this forward pass and its readback lie
outside the traced window; the program is compiled in set-up with the
epoch.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from harness import feed
from harness.spec import load_module
from reference import mla_moe_lm

tune = load_module("jobs", "tune")

# the flops module that counts this job's work
FLOPS = "mla_moe_lm_train"
IN_FLIGHT = tune.IN_FLIGHT


class Job(tune.Job):
    def __init__(self, cell, seed: int, spans):
        from repro.core import tuning
        from repro.federated import server
        from repro.models import model as M

        cfg, tr = cell.config, cell.traffic
        self.cell, self.seed, self.spans = cell, seed, spans
        self.arch = load_module("reference", cfg["model_type"]).arch(cfg)
        mcfg = load_module("systems", cfg["model_type"]).program_config(
            cfg).replace(use_pallas=bool(tr["use_pallas"]))
        self.shapes = mla_moe_lm.param_shapes(self.arch)
        self.batch, self.seq = int(tr["batch"]), int(tr["seq_len"])
        abstract = jax.eval_shape(lambda k: M.init_params(k, mcfg),
                                  jax.random.PRNGKey(0))
        flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)
        self.paths = [tune._path(p) for p, _ in flat]
        layout = {p: (tuple(x.shape), str(x.dtype))
                  for p, (_, x) in zip(self.paths, flat)}
        if layout != self.shapes:
            diff = sorted(set(layout.items()) ^ set(self.shapes.items()))
            raise RuntimeError(f"the program's weights are laid out "
                               f"otherwise than the reference's: {diff}")
        with spans("weights"):
            made = mla_moe_lm.make(seed, self.shapes)
            params = treedef.unflatten([made.pop(p) for p in self.paths])
            mask, opt = tuning.init_tuning(params)
        self.trainable = [p for p, m in zip(self.paths,
                                            jax.tree.leaves(mask)) if m]
        with spans("feed"):
            self.rows = feed.train_batches(seed, int(tr["pool"]), self.batch,
                                           self.seq, self.arch["V"], tr)
            self.pool = [{"tokens": jnp.asarray(r[None, :, :-1]),
                          "labels": jnp.asarray(r[None, :, 1:])}
                         for r in self.rows]
        epoch = server._tune_epoch_fn(mcfg, None, mask, 1, float(tr["lr"]), 0)
        with spans("compile"):
            self.epoch = epoch.lower(params, opt, self.pool[0]).compile()
            batch = {k: v[0] for k, v in self.pool[0].items()}
            self.load = jax.jit(lambda p, b: M.expert_load(p, mcfg, b)).lower(
                params, batch).compile()
        # the compiled program the window runs, for the trace reduction
        self.programs = [self.epoch.as_text()]
        self.params, self.opt, self.i = params, opt, 0
        self.readings = self._first_steps(int(tr["compare_steps"]),
                                          float(tr["b1"]))

    def _first_steps(self, n: int, b1: float) -> Dict:
        """Steps 1..n, which compile and warm up the program, and the
        program's readings of them."""
        with self.spans("first_steps"):
            losses = [self._step()]
            m = dict(zip(self.paths, jax.tree.leaves(self.opt["m"])))
            grad = {p: float(jnp.linalg.norm(m[p].astype(jnp.float32))
                             / (1.0 - b1)) for p in self.trainable}
            for _ in range(n - 1):
                losses.append(self._step())
            loss = [float(x[0]) for x in losses]
            p0 = mla_moe_lm.make(self.seed, self.shapes)
            now = dict(zip(self.paths, jax.tree.leaves(self.params)))
            change = {k: float(v) for k, v in
                      mla_moe_lm.change_norms(now, p0).items()}
            del p0
        return {"loss": loss, "grad": grad, "change": change}

    def window(self, seconds: float) -> Dict:
        out = super().window(seconds)
        last = {k: v[0] for k, v in
                self.pool[(self.i - 1) % len(self.pool)].items()}
        out["expert_load"] = lambda: np.asarray(
            self.load(self.params, last)).tolist()
        return out

    def free(self) -> None:
        super().free()
        self.load = None

    # -- correct ------------------------------------------------------------
    def reference(self, q=None) -> Dict:
        """The plain reference's readings of the same steps from the same
        weights and rows; ``q`` rounds its products (the control)."""
        tr = self.cell.traffic
        n = int(tr["compare_steps"])
        batches = [(jnp.asarray(r[:, :-1]), jnp.asarray(r[:, 1:]))
                   for r in self.rows[:n]]
        kw = {} if q is None else {"q": q}
        return mla_moe_lm.tune_readings(
            self.arch, lambda keep=None: mla_moe_lm.make(self.seed, {
                p: s for p, s in self.shapes.items()
                if keep is None or keep(p)}), batches, tune.hyper(tr), **kw)
