"""Phase III — global MoE model tuning (paper §IV.D).

FFN experts (routed *and* shared — the overwhelming majority of params)
are **frozen**; the embedding, self-attention, gate (router) and output
layers are fine-tuned on server-side public data.  The freeze mask feeds
``repro.optim.adamw``, whose frozen leaves carry scalar moments — the
"reduced memory footprint and faster convergence" claim of the paper.
"""
from __future__ import annotations

import logging
import re
from typing import Dict

import jax
import jax.numpy as jnp

from repro.models import model as M
from repro.models.config import ModelConfig
from repro.optim import adamw_init, adamw_update, scan_epoch
from repro.utils.pytree import flatten_with_paths, path_str

_FROZEN = re.compile(
    r"moe/(wi_gate|wi_up|wo|e_score_correction_bias)$|moe/shared/")


def expert_freeze_mask(params) -> Dict:
    """True = trainable.  Freezes routed + shared expert FFN weights and
    DeepSeek-V3's router correction bias: that bias has no gradient (it
    only selects) and moves by its own update rule, whose speed the
    DeepSeek-V3 report sets to 0 at the end of training; Phase III tunes
    an already trained MoE, so it stays as it is (weight decay would
    otherwise shrink it)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    mask = [not _FROZEN.search(path_str(p)) for p, _ in flat]
    return jax.tree_util.tree_unflatten(treedef, mask)


def trainable_fraction(params) -> float:
    mask = expert_freeze_mask(params)
    tot = sum(x.size for x in jax.tree.leaves(params))
    train = sum(x.size for x, m in
                zip(jax.tree.leaves(params), jax.tree.leaves(mask)) if m)
    return train / max(tot, 1)


_TAP_LOGGED: set = set()


def _log_taps(params, freeze_mask) -> None:
    """Log, once per distinct count, how many frozen leaves are tapped
    and the bytes of gradient the step no longer holds for them."""
    leaves = [p for p, m in zip(jax.tree.leaves(params),
                                jax.tree.leaves(freeze_mask)) if not m]
    key = (len(leaves), sum(p.size * p.dtype.itemsize for p in leaves))
    if key not in _TAP_LOGGED:
        _TAP_LOGGED.add(key)
        logging.getLogger(__name__).info(
            "frozen taps: %d leaves, %d gradient bytes not materialised",
            *key)


def make_tune_step(cfg: ModelConfig, freeze_mask, *, weight_decay=0.01,
                   mesh=None):
    """One Phase III step.  Only the trainable leaves are differentiated;
    each frozen leaf enters the loss as ``M.Tapped(weight, probe)``, and
    its probe's gradient is the squared norm of the weight's gradient,
    summed per layer inside the layer scan.  The clip norm covers the
    frozen leaves as if their gradients were held."""
    def step(params, opt_state, batch, lr):
        train = jax.tree.map(lambda p, m: p if m else None, params,
                             freeze_mask)
        probes = M.frozen_probes(params, freeze_mask)
        _log_taps(params, freeze_mask)

        def loss_of(train, probes):
            p = jax.tree.map(lambda w, t, pr: t if pr is None
                             else M.Tapped(w, pr), params, train, probes)
            return M.loss_fn(M.untap(p), cfg, batch, mesh=mesh)

        (loss, metrics), (grads, sq) = jax.value_and_grad(
            loss_of, argnums=(0, 1), has_aux=True)(train, probes)
        frozen_sq = sum(jnp.sum(s) for s in jax.tree.leaves(sq))
        params, opt_state, stats = adamw_update(
            grads, opt_state, params, lr=lr, weight_decay=weight_decay,
            freeze_mask=freeze_mask, frozen_sq=frozen_sq)
        metrics.update(stats)
        return params, opt_state, loss, metrics

    return step


def make_tune_epoch(cfg: ModelConfig, freeze_mask, *, steps, schedule,
                    weight_decay=0.01, mesh=None):
    """Scan-compiled multi-step tuning (see docs/loops.md): jit-able
    ``(params, opt_state, batches) -> (params, opt_state, losses)`` over
    stacked ``(steps, B, S)`` batches, lr schedule evaluated inside the
    scan — one host sync per Phase III epoch."""
    step_fn = make_tune_step(cfg, freeze_mask, weight_decay=weight_decay,
                             mesh=mesh)

    def carry_step(carry, b, lr):
        params, opt_state, loss, _ = step_fn(*carry, b, lr)
        return (params, opt_state), loss

    scanned = scan_epoch(carry_step, schedule, steps)

    def epoch(params, opt_state, batches):
        (params, opt_state), losses = scanned((params, opt_state), batches)
        return params, opt_state, losses

    return epoch


def init_tuning(params, *, state_dtype=None):
    mask = expert_freeze_mask(params)
    return mask, adamw_init(params, freeze_mask=mask, state_dtype=state_dtype)
