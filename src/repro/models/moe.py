"""Mixture-of-Experts FFN layer (routed + shared experts).

Execution paths:

* ``grouped`` — the one-device path: each token's top-k assignments are
  sorted by expert and every projection is one ``lax.ragged_dot`` over
  exactly T*k rows (dropless, no capacity, no padding).
* ``dense`` — every expert computes every token, combined with routing
  weights: E/top_k times the routed work.  Kept as the all-experts
  reference the tests compare the other paths against.
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
import logging
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental.xla_metadata import set_xla_metadata
from jax.sharding import PartitionSpec as P

from repro.kernels.moe_dispatch.ops import (capacity_positions,
                                            token_combine, token_dispatch)
from repro.models.config import ModelConfig
from repro.models import layers
from repro.utils import scopes


_PATH_LOGGED: set = set()


def _log_path(path: str, why: str) -> None:
    """Log which path a MoE layer takes, once per distinct reason (as
    ``moe_dispatch.dispatch_path`` does), so a run shows the path every
    MoE call took."""
    if (path, why) not in _PATH_LOGGED:
        _PATH_LOGGED.add((path, why))
        logging.getLogger(__name__).info("moe path: %s (%s)", path, why)


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
    if cfg.router_score == "sigmoid":
        p["e_score_correction_bias"] = jnp.zeros((E,), jnp.float32)
    return p


# ---------------------------------------------------------------------------
# routing
# ---------------------------------------------------------------------------

@scopes.scoped(scopes.ROUTER)
def route(p, cfg: ModelConfig, x, live=None, n_seq: int = 1):
    """Returns (weights (T,k), expert_idx (T,k), aux_loss scalar).

    x: (T, D) flat tokens, ``n_seq`` sequences of T / n_seq.
    Softmax-then-topk routing with the standard load-balance auxiliary
    loss (GShard / Switch style); ``router_score`` "sigmoid" takes
    DeepSeek-V3's router instead (``_sigmoid_route``).

    ``live`` (T,) bool marks rows that belong to live engine slots
    (serving): dead rows' routing weights are zeroed, so whatever a
    freed slot's garbage lane computes is combined with weight 0 — in
    concert with the ``valid=`` mask of ``capacity_positions`` this
    makes dead lanes invisible to every MoE path.
    """
    logits = x.astype(jnp.float32) @ p["router"]  # (T, E)
    if cfg.router_score == "sigmoid":
        return _sigmoid_route(p, cfg, logits, live, n_seq)
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


def _sigmoid_route(p, cfg: ModelConfig, logits, live, n_seq: int):
    """DeepSeek-V3's router (arXiv:2412.19437 §2.1.2, ``topk_method``
    noaux_tc): sigmoid scores; the top-k experts by score plus the
    correction bias, among the ``topk_group`` groups whose two best
    biased scores sum highest; as weights the unbiased scores of those
    experts, renormalised and scaled by ``routed_scaling_factor``.  The
    bias only selects: no gradient reaches it.

    The balance loss is the sequence-wise term (eq. 17-20): per sequence
    of S tokens, f_i = E/(k S) * (assignments to expert i) and P_i the
    mean over its tokens of the normalised scores; sum_i f_i P_i,
    averaged over the sequences."""
    T, E = logits.shape
    k, G = cfg.top_k, cfg.n_group
    scores = jax.nn.sigmoid(logits)
    sel = jax.lax.stop_gradient(scores + p["e_score_correction_bias"])
    if G > 1:
        g = sel.reshape(T, G, E // G)
        top2 = jax.lax.top_k(g, min(2, E // G))[0]
        _, keep = jax.lax.top_k(jnp.sum(top2, axis=-1), cfg.topk_group)
        kept = jnp.any(keep[:, :, None] == jnp.arange(G), axis=1)  # (T,G)
        sel = jnp.where(jnp.repeat(kept, E // G, axis=1), sel, -jnp.inf)
    _, idx = jax.lax.top_k(sel, k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * cfg.routed_scaling_factor
    if live is not None:
        w = jnp.where(live[:, None], w, 0.0)
    S = T // n_seq
    one_hot = jax.nn.one_hot(idx, E, dtype=jnp.float32)  # (T,k,E)
    f = (E / (k * S)) * jnp.sum(one_hot.reshape(n_seq, S * k, E), axis=1)
    probs = scores / jnp.sum(scores, axis=-1, keepdims=True)
    P = jnp.mean(probs.reshape(n_seq, S, E), axis=1)
    return w, idx, cfg.router_aux_coef * jnp.mean(jnp.sum(f * P, axis=-1))


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
# dense path (the all-experts reference)
# ---------------------------------------------------------------------------

def moe_dense(p, cfg: ModelConfig, x, live=None):
    """x: (B, S, D).  Computes every expert on every token and selects
    each token's top-k with a one-hot combine: the reference the tests
    hold ``moe_grouped`` and the sharded paths to.  Routing is per-token
    here, so ``live`` only zeroes dead rows' combine weights (no
    cross-row capacity to protect)."""
    B, S, D = x.shape
    xt = x.reshape(-1, D)
    w, idx, aux = route(p, cfg, xt,
                        None if live is None else live.reshape(-1), B)
    with jax.named_scope(scopes.EXPERTS):
        # (E, T, D) all-experts compute
        h = jnp.einsum("td,edf->etf", xt, p["wi_gate"])
        h = layers._act(cfg, h) * jnp.einsum("td,edf->etf", xt, p["wi_up"])
        y_all = jnp.einsum("etf,efd->etd", h, p["wo"])  # (E, T, D)
        one_hot = jax.nn.one_hot(idx, cfg.n_experts, dtype=xt.dtype)  # (T,k,E)
        comb = jnp.einsum("tk,tke->te", w.astype(xt.dtype), one_hot)
        out = jnp.einsum("te,etd->td", comb, y_all)
    out = out.reshape(B, S, D)
    return _with_shared(p, cfg, x, out), aux


# ---------------------------------------------------------------------------
# grouped path (the one-device default)
# ---------------------------------------------------------------------------

# XLA's TPU ragged dot takes its tiles (rows, contracted width, output
# width) from the frontend attribute ``ragged_dot_tiling``; the row tile
# must divide the rows.  Its default at Qwen1.5-MoE widths (16384 rows
# over 60 experts, 2048 <-> 1408) pads every expert's rows to 512-row
# tiles.  A sweep on one TPU v5e found 256-row tiles for the products,
# 128-row tiles for the weight gradient, and 1024-wide tiles where 1024
# divides a width (512 where not) 1.8-2.5x faster than that default.
_ROWS_TILE, _WEIGHT_GRAD_ROWS_TILE = 256, 128
_DRHS = jax.lax.RaggedDotDimensionNumbers(
    dot_dimension_numbers=(((0,), (0,)), ((), ())),
    lhs_ragged_dimensions=(0,), rhs_group_dimensions=())


def _tiles(rows_tile: int, m: int, k: int, n: int):
    """Context giving an (m, k) x (k, n) ragged dot its tiles; XLA's own
    where ``rows_tile`` does not divide m."""
    if m % rows_tile:
        return set_xla_metadata()

    def wide(d):
        return 1024 if d % 1024 == 0 else 512
    return set_xla_metadata(
        ragged_dot_tiling=f"{rows_tile},{wide(k)},{wide(n)}")


def _ragged(x, w, group_sizes):
    """(M, K) rows sorted by group x (G, K, N): rows of group g times w[g]."""
    with _tiles(_ROWS_TILE, x.shape[0], *w.shape[1:]):
        return jax.lax.ragged_dot(x, w, group_sizes)


@jax.custom_vjp
def _grouped_matmul(x, w, group_sizes):
    """``lax.ragged_dot`` whose input and weight gradients are ragged dots
    with tiles of their own (autodiff would give them the forward's)."""
    return _ragged(x, w, group_sizes)


def _grouped_matmul_fwd(x, w, group_sizes):
    return _ragged(x, w, group_sizes), (x, w, group_sizes)


def _grouped_matmul_bwd(res, g):
    x, w, group_sizes = res
    dx = _ragged(g, jnp.swapaxes(w, 1, 2), group_sizes)
    with _tiles(_WEIGHT_GRAD_ROWS_TILE, *x.shape, g.shape[1]):
        dw = jax.lax.ragged_dot_general(x, g, group_sizes, _DRHS)
    return dx, dw.astype(w.dtype), None


_grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _take_rows(k: int, x, src, back):
    """``x[src]``, where ``src`` sends each row r of x to k rows of the
    result, listed in ``back[r*k:(r+1)*k]``.  The gradient gathers by
    ``back`` and sums over k in float32, where autodiff would scatter-add
    into x."""
    return x[src]


def _take_rows_fwd(k, x, src, back):
    return x[src], back


def _take_rows_bwd(k, back, g):
    gx = g[back].reshape(-1, k, g.shape[-1]).astype(jnp.float32).sum(axis=1)
    return gx.astype(g.dtype), None, None


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


def moe_grouped(p, cfg: ModelConfig, x, live=None):
    """x: (B, S, D).  Dropless routed experts on one device.

    The T*k (token, expert) assignments are sorted by expert, the tokens
    gathered in that order, and each projection is one grouped matmul
    (``lax.ragged_dot``) of the sorted rows against the stacked expert
    weights, group e holding expert e's rows.  The inverse permutation
    gathers the rows back to (T, k, D), summed under the float32 routing
    weights.  ``live`` zeroes dead rows' weights, as in ``moe_dense``:
    with no capacity there is nothing more to protect."""
    B, S, D = x.shape
    E = cfg.n_experts
    xt = x.reshape(-1, D)
    w, idx, aux = route(p, cfg, xt,
                        None if live is None else live.reshape(-1), B)
    T, k = idx.shape
    with jax.named_scope(scopes.EXPERTS):
        with jax.named_scope(scopes.DISPATCH):
            flat_e = idx.reshape(-1)
            order = jnp.argsort(flat_e, stable=True)   # sorted -> assignment
            inv = jnp.argsort(order)                   # assignment -> sorted
            group_sizes = jnp.bincount(flat_e, length=E).astype(jnp.int32)
            xs = _take_rows(k, xt, order // k, inv)          # (T*k, D)
        with jax.named_scope(scopes.FFN):
            h = layers._act(cfg, _grouped_matmul(xs, p["wi_gate"],
                                                 group_sizes))
            h = h * _grouped_matmul(xs, p["wi_up"], group_sizes)
            ys = _grouped_matmul(h, p["wo"], group_sizes)    # (T*k, D)
        with jax.named_scope(scopes.COMBINE):
            y = _take_rows(1, ys, inv, order).reshape(T, k, D)
            out = jnp.sum(y.astype(jnp.float32) * w[:, :, None], axis=1)
            out = out.astype(xt.dtype)
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
    w, idx, aux = route(p, cfg, xt, None if live is None else live_t, B)

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
    w, idx, aux = route(p, cfg, xt, None if live is None else live_t, B)
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

    ``moe_impl="auto"`` takes ``a2a`` on a mesh with a "model" axis and
    more than one device, else ``grouped``.
    """
    impl, why = cfg.moe_impl, "moe_impl"
    if impl == "auto":
        if (mesh is not None and "model" in mesh.axis_names
                and mesh.size > 1):
            impl, why = "a2a", "model axis"
        else:
            impl, why = "grouped", "no model axis"
    if impl == "grouped":
        B, S, _ = x.shape
        _log_path(impl, f"{why}; {B * S * cfg.top_k} assignments over "
                        f"{cfg.n_experts} experts, dropless")
        return moe_grouped(p, cfg, x, live)
    _log_path(impl, why)
    if impl == "replicated_ep":
        return moe_replicated_ep(p, cfg, x, mesh, live)
    if impl == "a2a":
        data_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        return moe_a2a(p, cfg, x, mesh, data_axes=data_axes, live=live)
    return moe_dense(p, cfg, x, live)
