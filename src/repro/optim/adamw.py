"""AdamW in pure JAX, with parameter-freezing masks and moment policies.

The freeze mask is central to the paper's Phase III (global MoE tuning):
the FFN experts — the overwhelming majority of parameters — stay frozen
while gate / embedding / attention / output layers train (DeepFusion
§IV.D).  Frozen leaves carry **scalar** zero moments, so the optimizer
state for a frozen 671B-expert bank is a few bytes, mirroring the paper's
"reduced memory footprint" claim.

Moment storage is governed by a ``quant.MomentPolicy`` (the optimizer
analogue of the cache ``CachePolicy``): the first moment in fp32 or
bf16, the second in fp32 / bf16 / int8 with one per-tensor float32
scale.  Like the cache, **structure carries policy**: an int8-v state
carries a ``"v_scale"`` tree and ``adamw_update`` detects it
structurally, so compiled training loops (``scan_epoch``, the vmapped
fleet driver) need no policy plumbing — they retrace per state
structure.  The master-weight update math is unchanged: moments are
dequantized to fp32, updated, and re-quantized per step, which is what
lets the fleet driver host measurably more devices per host at equal
bytes.

``state_dtype`` remains as the legacy spelling of a uniform moment
dtype (bf16 both moments); ``policy`` supersedes it.
"""
from __future__ import annotations

from typing import Callable, Optional, Union

import jax
import jax.numpy as jnp

from repro.models import quant
from repro.utils import scopes


def _is_frozen(mask_leaf) -> bool:
    return mask_leaf is False


def resolve_moment_policy(policy) -> quant.MomentPolicy:
    """Accepts a ``MomentPolicy``, a shorthand string, or None.

    Shorthands: ``""`` (fp32 everything), ``"bf16"`` (both moments
    bf16), ``"int8"`` (m bf16, v int8 + per-tensor scale — the smallest
    state that still tracks fp32 training, see tests/test_quantized.py).
    """
    if policy is None:
        return quant.MomentPolicy()
    if isinstance(policy, quant.MomentPolicy):
        return policy
    if policy == "":
        return quant.MomentPolicy()
    if policy == "bf16":
        return quant.MomentPolicy("bf16", "bf16")
    if policy == "int8":
        return quant.MomentPolicy("bf16", "int8")
    raise ValueError(f"unknown moment policy {policy!r} "
                     "(expected '', 'bf16', 'int8', or a MomentPolicy)")


def adamw_init(params, *, freeze_mask=None, state_dtype=None, policy=None):
    """freeze_mask: pytree of bools matching params (True = trainable).

    ``policy`` (a ``quant.MomentPolicy`` or shorthand string) sets the
    moment storage dtypes; int8 second moments add a ``"v_scale"`` tree
    of scalar float32 scales to the returned state."""
    if freeze_mask is None:
        freeze_mask = jax.tree.map(lambda _: True, params)
    pol = resolve_moment_policy(policy)
    if state_dtype is not None and policy is None:
        m_dt = v_dt = state_dtype
    else:
        m_dt, v_dt = pol.m_storage(), pol.v_storage()

    def mom(dt):
        def init(p, trainable):
            if not trainable:
                return jnp.zeros((), dt)
            return jnp.zeros(p.shape, dt)
        return init

    state = {
        "m": jax.tree.map(mom(m_dt), params, freeze_mask),
        "v": jax.tree.map(mom(v_dt), params, freeze_mask),
        "step": jnp.zeros((), jnp.int32),
    }
    if pol.v_quantized:
        state["v_scale"] = jax.tree.map(
            lambda _: jnp.zeros((), jnp.float32), params)
    return state


def global_norm_clip(grads, max_norm: float):
    leaves = [jnp.sum(jnp.square(g.astype(jnp.float32)))
              for g in jax.tree.leaves(grads)]
    gnorm = jnp.sqrt(sum(leaves))
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(gnorm, 1e-9))
    return jax.tree.map(lambda g: (g * scale).astype(g.dtype), grads), gnorm


@scopes.scoped(scopes.OPTIMIZER)
def adamw_update(grads, state, params, *, lr, b1: float = 0.9,
                 b2: float = 0.95, eps: float = 1e-8,
                 weight_decay: float = 0.0, freeze_mask=None,
                 clip_norm: float = 1.0):
    """One AdamW step.  Returns (new_params, new_state, stats).

    A state carrying ``"v_scale"`` (int8 second moments) is dequantized
    to fp32 before the update and re-quantized after — the update math
    itself always runs in fp32 master precision."""
    if freeze_mask is None:
        freeze_mask = jax.tree.map(lambda _: True, params)
    v_quantized = "v_scale" in state
    step = state["step"] + 1
    if clip_norm:
        grads, gnorm = global_norm_clip(grads, clip_norm)
    else:
        gnorm = jnp.zeros((), jnp.float32)
    c1 = 1.0 - b1 ** step.astype(jnp.float32)
    c2 = 1.0 - b2 ** step.astype(jnp.float32)

    def upd(p, g, m, v, vs, trainable):
        if not trainable:
            return p, m, v, vs
        gf = g.astype(jnp.float32)
        m_new = b1 * m.astype(jnp.float32) + (1 - b1) * gf
        vf = quant.dequantize_v(v, vs) if v_quantized \
            else v.astype(jnp.float32)
        v_new = b2 * vf + (1 - b2) * jnp.square(gf)
        mhat = m_new / c1
        vhat = v_new / c2
        delta = mhat / (jnp.sqrt(vhat) + eps)
        if weight_decay:
            delta = delta + weight_decay * p.astype(jnp.float32)
        p_new = (p.astype(jnp.float32) - lr * delta).astype(p.dtype)
        if v_quantized:
            v_q, vs_new = quant.quantize_v(v_new)
            return p_new, m_new.astype(m.dtype), v_q, vs_new
        return p_new, m_new.astype(m.dtype), v_new.astype(v.dtype), vs

    vscale = state.get("v_scale")
    if vscale is None:
        vscale = jax.tree.map(lambda _: jnp.zeros((), jnp.float32), params)
    out = jax.tree.map(upd, params, grads, state["m"], state["v"], vscale,
                       freeze_mask)
    # unzip the 4-tuples
    treedef = jax.tree.structure(params)
    flat = treedef.flatten_up_to(out)
    new_params = treedef.unflatten([t[0] for t in flat])
    new_state = {
        "m": treedef.unflatten([t[1] for t in flat]),
        "v": treedef.unflatten([t[2] for t in flat]),
        "step": step,
    }
    if v_quantized:
        new_state["v_scale"] = treedef.unflatten([t[3] for t in flat])
    return new_params, new_state, {"grad_norm": gnorm}
