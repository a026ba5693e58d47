"""Phase III (DeepFusion §IV.D): tune the global MoE with its expert FFNs
frozen, through the jitted epoch the federated server builds
(``federated.server._tune_epoch_fn`` over ``core.tuning``).

Set-up makes the weights from the seed, builds that one epoch program
with its state, and drives it through its first steps on the window's
own rows; those steps give the program's readings for ``correct``.  The
window then runs the same object on, epoch after epoch, with
``IN_FLIGHT`` epochs queued while the host waits on the oldest.  An epoch is one step,
so that the optimizer's state after the first step can be read: its
first moment is the first gradient as the optimizer got it.
"""
from __future__ import annotations

import collections
import time
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from harness import compare, feed
from harness.spec import load_module
from reference import moe_lm, weights

# the flops module that counts this job's work
FLOPS = "moe_lm_train"
# steps queued on the device while the host waits on the oldest: a host
# pause shorter than the steps queued leaves the device busy
IN_FLIGHT = 3


def _path(keys) -> str:
    return "/".join(str(getattr(k, "key", k)) for k in keys)


def hyper(traffic) -> Dict[str, float]:
    return {k: float(traffic[k]) for k in ("lr", "b1", "b2", "eps", "wd",
                                           "clip")}


class Job:
    def __init__(self, cell, seed: int, spans):
        from repro.core import tuning
        from repro.federated import server
        from repro.models import model as M

        cfg, tr = cell.config, cell.traffic
        self.cell, self.seed, self.spans = cell, seed, spans
        self.arch = load_module("reference", cfg["model_type"]).arch(cfg)
        mcfg = load_module("systems", cfg["model_type"]).program_config(
            cfg).replace(use_pallas=bool(tr["use_pallas"]))
        self.shapes = moe_lm.param_shapes(self.arch)
        self.batch, self.seq = int(tr["batch"]), int(tr["seq_len"])
        abstract = jax.eval_shape(lambda k: M.init_params(k, mcfg),
                                  jax.random.PRNGKey(0))
        flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)
        self.paths = [_path(p) for p, _ in flat]
        layout = {p: (tuple(x.shape), str(x.dtype))
                  for p, (_, x) in zip(self.paths, flat)}
        if layout != self.shapes:
            diff = sorted(set(layout.items()) ^ set(self.shapes.items()))
            raise RuntimeError(f"the program's weights are laid out "
                               f"otherwise than the reference's: {diff}")
        with spans("weights"):
            made = weights.make(seed, self.shapes)
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
        # the compiled programs the window runs, for the trace reduction
        self.programs = [self.epoch.as_text()]
        self.params, self.opt, self.i = params, opt, 0
        self.readings = self._first_steps(int(tr["compare_steps"]),
                                          float(tr["b1"]))

    # -- the window's own call --------------------------------------------
    def _step(self):
        b = self.pool[self.i % len(self.pool)]
        self.i += 1
        self.params, self.opt, losses = self.epoch(self.params, self.opt, b)
        return losses

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
            p0 = weights.make(self.seed, self.shapes)
            now = dict(zip(self.paths, jax.tree.leaves(self.params)))
            change = {k: float(v) for k, v in
                      moe_lm.change_norms(now, p0).items()}
            del p0
        return {"loss": loss, "grad": grad, "change": change}

    def window(self, seconds: float) -> Dict:
        t0 = time.perf_counter()
        pending, done, ends = collections.deque(), [], []
        while True:
            while len(pending) < IN_FLIGHT:
                with self.spans("dispatch"):
                    pending.append(self._step())
            with self.spans("wait"):
                pending[0].block_until_ready()
            done.append(pending.popleft())
            ends.append(time.perf_counter())
            if ends[-1] - t0 >= seconds:
                break
        while pending:
            with self.spans("wait"):
                pending[0].block_until_ready()
            done.append(pending.popleft())
            ends.append(time.perf_counter())
        elapsed = ends[-1] - t0
        gaps = np.diff([t0] + ends)
        losses = np.concatenate([np.asarray(x) for x in done])
        tokens = len(done) * self.batch * self.seq
        return {"elapsed_s": elapsed, "attempted": len(done),
                "failed": int(np.sum(~np.isfinite(losses))),
                "end_to_end": {"train_tokens_per_s": tokens / elapsed},
                "steps": len(done), "tokens": tokens,
                "note": f"step to step: median {np.median(gaps):.6f} s, "
                        f"longest {gaps.max():.6f} s"}

    def work(self) -> Dict:
        """What the flops module needs to count one step's work."""
        return {"arch": self.arch, "batch": self.batch, "seq": self.seq,
                "train": True, "teacher": None}

    def free(self) -> None:
        self.params = self.opt = self.pool = self.epoch = None
        self.programs = []

    # -- correct ------------------------------------------------------------
    def reference(self, q=None) -> Dict:
        """The plain reference's readings of the same steps from the same
        weights and rows; ``q`` rounds its products (the control)."""
        tr = self.cell.traffic
        n = int(tr["compare_steps"])
        batches = [(jnp.asarray(r[:, :-1]), jnp.asarray(r[:, 1:]))
                   for r in self.rows[:n]]
        kw = {} if q is None else {"q": q}
        return moe_lm.tune_readings(
            self.arch, lambda keep=None: weights.make(self.seed, {
                p: s for p, s in self.shapes.items()
                if keep is None or keep(p)}), batches, hyper(tr), **kw)

    def check(self):
        return compare.training(self.readings, self.reference(),
                                self.cell.limits)
