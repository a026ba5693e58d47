"""Mixture-of-Experts FFN layer (routed + shared experts).

Two execution paths:

* ``dense`` — every expert computes every token, combined with routing
  weights.  O(E) waste; used only for tiny CPU test configs (E <= 8).
* ``a2a``  — TPU-native expert parallelism inside ``shard_map``: tokens
  live on the "data" axis, experts are sharded over the "model" axis.
  Each device packs its tokens into fixed-capacity per-expert buffers,
  a ``lax.all_to_all`` over "model" moves them to the expert owners, a
  batched (E_local, cap, D) x (E_local, D, F) einsum runs the expert
  FFNs on the MXU, and the reverse all_to_all brings results home.
  Capacity overflow drops tokens (GShard semantics, residual passes
  through).  This is the mapping of the paper's DeepSpeed-MoE server
  onto ICI collectives instead of NCCL.

Experts whose count does not divide the "model" axis are padded with
dummy experts whose router logits are masked to -inf.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels.moe_dispatch.ops import (capacity_positions,
                                            token_combine, token_dispatch)
from repro.models.config import ModelConfig
from repro.models import layers
from repro.utils import scopes


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------

def init_moe(key, cfg: ModelConfig, dtype):
    D = cfg.d_model
    F = cfg.moe_d_ff or cfg.d_ff
    E = cfg.n_experts
    ks = jax.random.split(key, 5)
    p = {
        "router": layers.dense_init(ks[0], (D, E), 0, jnp.float32),
        "wi_gate": layers.dense_init(ks[1], (E, D, F), 1, dtype),
        "wi_up": layers.dense_init(ks[2], (E, D, F), 1, dtype),
        "wo": layers.dense_init(ks[3], (E, F, D), 1, dtype),
    }
    if cfg.n_shared_experts:
        p["shared"] = layers.init_mlp(ks[4], cfg, D, F * cfg.n_shared_experts, dtype)
    return p


# ---------------------------------------------------------------------------
# routing
# ---------------------------------------------------------------------------

@scopes.scoped(scopes.ROUTER)
def route(p, cfg: ModelConfig, x, live=None):
    """Returns (weights (T,k), expert_idx (T,k), aux_loss scalar).

    x: (T, D) flat tokens.  Softmax-then-topk routing with the standard
    load-balance auxiliary loss (GShard / Switch style).

    ``live`` (T,) bool marks rows that belong to live engine slots
    (serving): dead rows' routing weights are zeroed, so whatever a
    freed slot's garbage lane computes is combined with weight 0 — in
    concert with the ``valid=`` mask of ``capacity_positions`` this
    makes dead lanes invisible to every MoE path.
    """
    logits = x.astype(jnp.float32) @ p["router"]  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, cfg.top_k)
    w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-9)
    if live is not None:
        w = jnp.where(live[:, None], w, 0.0)
    # aux load-balance loss: E * sum_e f_e * p_e
    E = cfg.n_experts
    me = jnp.mean(probs, axis=0)  # mean router prob per expert
    one_hot = jax.nn.one_hot(idx, E, dtype=jnp.float32)  # (T,k,E)
    fe = jnp.mean(jnp.sum(one_hot, axis=1), axis=0)  # fraction routed per expert
    aux = E * jnp.sum(me * fe) * cfg.router_aux_coef
    return w, idx, aux


def _expert_ffn(cfg: ModelConfig, wg, wu, wo, x):
    """Batched expert FFN: x (E, C, D), weights (E, D, F)/(E, F, D)."""
    h = layers._act(cfg, jnp.einsum("ecd,edf->ecf", x, wg))
    h = h * jnp.einsum("ecd,edf->ecf", x, wu)
    return jnp.einsum("ecf,efd->ecd", h, wo)


def _with_shared(p, cfg: ModelConfig, x, out):
    """The routed experts' ``out`` plus the shared experts' MLP of ``x``,
    where the layer has shared experts."""
    if not cfg.n_shared_experts:
        return out
    with jax.named_scope(scopes.SHARED_EXPERT):
        return out + layers.apply_mlp(p["shared"], cfg, x)


# ---------------------------------------------------------------------------
# dense path (tests / tiny configs)
# ---------------------------------------------------------------------------

def moe_dense(p, cfg: ModelConfig, x, live=None):
    """x: (B, S, D).  Computes all experts on all tokens (small E only).
    Routing is per-token here, so ``live`` only zeroes dead rows'
    combine weights (no cross-row capacity to protect)."""
    B, S, D = x.shape
    xt = x.reshape(-1, D)
    w, idx, aux = route(p, cfg, xt,
                        None if live is None else live.reshape(-1))
    with jax.named_scope(scopes.EXPERTS):
        if cfg.use_pallas:
            from repro.kernels.moe_gemm import ops as moe_ops
            out = moe_ops.moe_ffn(xt, w, idx, p["wi_gate"], p["wi_up"],
                                  p["wo"], act=cfg.act)
        else:
            # (E, T, D) all-experts compute
            h = jnp.einsum("td,edf->etf", xt, p["wi_gate"])
            h = layers._act(cfg, h) * jnp.einsum("td,edf->etf", xt,
                                                 p["wi_up"])
            y_all = jnp.einsum("etf,efd->etd", h, p["wo"])  # (E, T, D)
            one_hot = jax.nn.one_hot(idx, cfg.n_experts,
                                     dtype=xt.dtype)  # (T,k,E)
            comb = jnp.einsum("tk,tke->te", w.astype(xt.dtype), one_hot)
            out = jnp.einsum("te,etd->td", comb, y_all)
    out = out.reshape(B, S, D)
    return _with_shared(p, cfg, x, out), aux


# ---------------------------------------------------------------------------
# all-to-all expert-parallel path (shard_map over the "model" axis)
# ---------------------------------------------------------------------------

def _pad_experts(E: int, ep: int) -> int:
    return -(-E // ep) * ep


def _expert_weights(p, E_pad: int):
    """The routed experts' (wi_gate, wi_up, wo), padded with zero
    experts up to ``E_pad``."""
    ws = (p["wi_gate"], p["wi_up"], p["wo"])
    padn = E_pad - ws[0].shape[0]
    if not padn:
        return ws
    return tuple(jnp.pad(x, ((0, padn), (0, 0), (0, 0))) for x in ws)


def _capacity(cfg: ModelConfig, t_loc: int, E_pad: int, *, align: int) -> int:
    """Per-(source device, expert) buffer slots.  ``moe_dropless`` sizes
    for the worst case (every local assignment hits one expert) so the
    keep mask can never drop a token — serving's requirement; the
    default is the GShard ``capacity_factor`` drop tradeoff."""
    if cfg.moe_dropless:
        cap = max(t_loc * cfg.top_k, 1)
    else:
        cap = max(int(math.ceil(t_loc * cfg.top_k * cfg.capacity_factor
                                / E_pad)), 4)
    return -(-cap // align) * align


def _a2a_dispatch(xt, flat_tok, slot, keep, *, cfg: ModelConfig,
                  ep_axis: str, ep_size: int, E_loc: int, cap: int):
    """Stage 1: pack tokens into per-(device, expert, capacity-slot)
    buffers and all_to_all them to their expert owners."""
    D = xt.shape[1]
    buf = token_dispatch(xt, flat_tok, slot, keep, ep_size * E_loc * cap,
                         use_kernel=cfg.use_pallas)
    buf = buf.reshape(ep_size, E_loc * cap, D)
    recv = jax.lax.all_to_all(buf, ep_axis, split_axis=0, concat_axis=0,
                              tiled=False)       # (ep_size, E_loc*cap, D)
    recv = recv.reshape(ep_size, E_loc, cap, D).transpose(1, 0, 2, 3)
    return recv.reshape(E_loc, ep_size * cap, D)


def _a2a_ffn(recv, wg, wu, wo, *, cfg: ModelConfig):
    """Stage 2: batched expert FFN on the owner device (MXU einsum or
    the Pallas grouped kernel)."""
    if cfg.use_pallas:
        from repro.kernels.moe_gemm import ops as moe_ops
        return moe_ops.grouped_ffn(recv, wg, wu, wo, act=cfg.act)
    return _expert_ffn(cfg, wg, wu, wo, recv)


def _a2a_combine(y, flat_tok, slot, keep, w, n_tokens, *, cfg: ModelConfig,
                 ep_axis: str, ep_size: int, E_loc: int, cap: int):
    """Stage 3: reverse all_to_all and weighted unpack back to tokens."""
    D = y.shape[-1]
    y = y.reshape(E_loc, ep_size, cap, D).transpose(1, 0, 2, 3)
    y = y.reshape(ep_size, E_loc * cap, D)
    back = jax.lax.all_to_all(y, ep_axis, split_axis=0, concat_axis=0,
                              tiled=False)       # (ep_size, E_loc*cap, D)
    return token_combine(back.reshape(ep_size * E_loc * cap, D), flat_tok,
                         slot, keep, w.reshape(-1), n_tokens,
                         use_kernel=cfg.use_pallas)


def _a2a_local(xt, w, idx, live, wg, wu, wo, *, cfg: ModelConfig,
               ep_axis: str, ep_size: int, capacity: int):
    """Per-device body under shard_map: dispatch / FFN / combine stages
    (split so an overlapped decode step can interleave the all_to_alls
    of one batch half with the attention compute of the other).

    xt:  (T_loc, D) local tokens            [sharded over "data"]
    idx: (T_loc, k) global expert ids       [local]
    live: (T_loc,) bool liveness mask       [sharded over "data"]
    wg/wu/wo: (E_loc, D, F) local expert weights [sharded over "model"]
    """
    T, D = xt.shape
    k = idx.shape[1]
    E_loc = wg.shape[0]
    cap = capacity
    stage = dict(cfg=cfg, ep_axis=ep_axis, ep_size=ep_size, E_loc=E_loc,
                 cap=cap)

    with jax.named_scope(scopes.DISPATCH):
        # --- routing layout: per (destination device, local expert, slot)
        flat_e = idx.reshape(-1)                 # (T*k,) global expert id
        flat_tok = jnp.arange(T * k, dtype=jnp.int32) // k
        # dead rows (freed engine slots) neither hold a capacity rank nor
        # survive the keep mask: they cannot steal an expert's capacity
        # from a live token on any device
        pos, keep = capacity_positions(flat_e, cap,
                                       valid=jnp.repeat(live, k))
        # flat buffer layout: (ep_size * E_loc * cap); dest device major
        slot = flat_e * cap + pos                # == dest*(E_loc*cap) + ...
        recv = _a2a_dispatch(xt, flat_tok, slot, keep, **stage)
    with jax.named_scope(scopes.FFN):
        y = _a2a_ffn(recv, wg, wu, wo, cfg=cfg)  # (E_loc, ep*cap, D)
    with jax.named_scope(scopes.COMBINE):
        out = _a2a_combine(y, flat_tok, slot, keep, w, T, **stage)
        return out.astype(xt.dtype)


def moe_a2a(p, cfg: ModelConfig, x, mesh, *, data_axes=("data",),
            ep_axis: str = "model", live=None):
    """x: (B, S, D) with batch sharded over `data_axes`.  ``live``
    (B, S) bool masks dead serving lanes out of routing weights AND
    per-device capacity ranks (see ``_a2a_local``)."""
    B, S, D = x.shape
    E = cfg.n_experts
    ep_size = mesh.shape[ep_axis]
    E_pad = _pad_experts(E, ep_size)
    E_loc = E_pad // ep_size

    xt = x.reshape(-1, D)
    live_t = (jnp.ones((B * S,), jnp.bool_) if live is None
              else live.reshape(-1))
    w, idx, aux = route(p, cfg, xt, None if live is None else live_t)

    # static per-device capacity: tokens_per_device * k * cf / E_pad
    n_data = 1
    for a in data_axes:
        n_data *= mesh.shape[a]
    if (B * S) % n_data != 0:
        # tiny decode batches (e.g. long_500k, B*S=1) replicate tokens;
        # the a2a round-trip still lands every token on its expert owner.
        data_axes, n_data = (), 1
    t_loc = max((B * S) // n_data, 1)
    cap = _capacity(cfg, t_loc, E_pad, align=8)  # MXU-aligned

    if not data_axes:
        dspec = P(None)
    elif len(data_axes) > 1:
        dspec = P(data_axes)
    else:
        dspec = P(data_axes[0])
    body = functools.partial(_a2a_local, cfg=cfg, ep_axis=ep_axis,
                             ep_size=ep_size, capacity=cap)
    with jax.named_scope(scopes.EXPERTS):
        wg, wu, wo = _expert_weights(p, E_pad)
        out = jax.shard_map(
            body, mesh=mesh,
            in_specs=(dspec, dspec, dspec, dspec,
                      P(ep_axis), P(ep_axis), P(ep_axis)),
            out_specs=dspec,
            check_vma=False,
        )(xt, w, idx, live_t, wg, wu, wo)

    out = out.reshape(B, S, D)
    return _with_shared(p, cfg, x, out), aux


def _replicated_ep_local(xt, w, idx, live, wg, wu, wo, *, cfg: ModelConfig,
                         axes, capacity: int):
    """Serving-layout expert parallelism: tokens REPLICATED on every
    device, experts sharded 1-per-device across ALL mesh axes, outputs
    combined with one small psum.  No weight collectives at all — the
    layout that makes 671B-class MoE decode ICI-cheap (EXPERIMENTS.md
    §Perf, iteration D2)."""
    T, D = xt.shape
    k = idx.shape[1]
    E_loc = wg.shape[0]
    cap = capacity
    dev = jax.lax.axis_index(axes)

    with jax.named_scope(scopes.DISPATCH):
        flat_e = idx.reshape(-1)
        flat_tok = jnp.arange(T * k, dtype=jnp.int32) // k
        pos, fits = capacity_positions(flat_e, cap,
                                       valid=jnp.repeat(live, k))
        local = (flat_e // E_loc) == dev
        keep = local & fits
        slot = jnp.where(local, flat_e % E_loc, 0) * cap + pos
        buf = token_dispatch(xt, flat_tok, slot, keep, E_loc * cap,
                             use_kernel=cfg.use_pallas)
        buf = buf.reshape(E_loc, cap, D)
    with jax.named_scope(scopes.FFN):
        if cfg.use_pallas:
            from repro.kernels.moe_gemm import ops as moe_ops
            y = moe_ops.grouped_ffn(buf, wg, wu, wo, act=cfg.act)
        else:
            y = _expert_ffn(cfg, wg, wu, wo, buf)
    with jax.named_scope(scopes.COMBINE):
        out = token_combine(y.reshape(E_loc * cap, D), flat_tok, slot, keep,
                            w.reshape(-1), T, use_kernel=cfg.use_pallas)
        return jax.lax.psum(out.astype(xt.dtype), axes)


def moe_replicated_ep(p, cfg: ModelConfig, x, mesh, live=None):
    """Decode-path MoE: see _replicated_ep_local."""
    B, S, D = x.shape
    E = cfg.n_experts
    n_dev = mesh.size
    axes = tuple(mesh.axis_names)
    E_pad = _pad_experts(E, n_dev)
    E_loc = E_pad // n_dev

    xt = x.reshape(-1, D)
    live_t = (jnp.ones((B * S,), jnp.bool_) if live is None
              else live.reshape(-1))
    w, idx, aux = route(p, cfg, xt, None if live is None else live_t)
    T = xt.shape[0]
    if cfg.moe_dropless:
        cap = _capacity(cfg, T, E_pad, align=4)
    else:
        cap = max(int(math.ceil(T * cfg.top_k * cfg.capacity_factor
                                / E_pad)), 4)
        cap = min(-(-cap // 4) * 4, max(T, 4))

    body = functools.partial(_replicated_ep_local, cfg=cfg, axes=axes,
                             capacity=cap)
    espec = P(axes)
    with jax.named_scope(scopes.EXPERTS):
        wg, wu, wo = _expert_weights(p, E_pad)
        out = jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(None), P(None), P(None), P(None), espec, espec,
                      espec),
            out_specs=P(None),
            check_vma=False,
        )(xt, w, idx, live_t, wg, wu, wo)
    out = out.reshape(B, S, D)
    return _with_shared(p, cfg, x, out), aux


def apply_moe(p, cfg: ModelConfig, x, mesh=None, live=None):
    """Dispatch to a MoE execution path.

    ``live`` (B, S) bool is the serving liveness mask: rows of freed
    engine slots are zeroed out of routing weights and excluded from
    per-device expert-capacity accounting on every path.  None (the
    training / prefill default) means all rows are live and is
    bit-identical to the pre-mask behavior.
    """
    impl = cfg.moe_impl
    if impl == "auto":
        impl = "a2a" if (mesh is not None and "model" in mesh.axis_names
                         and mesh.size > 1) else "dense"
    if impl == "replicated_ep":
        return moe_replicated_ep(p, cfg, x, mesh, live)
    if impl == "a2a":
        data_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        return moe_a2a(p, cfg, x, mesh, data_axes=data_axes, live=live)
    return moe_dense(p, cfg, x, live)
