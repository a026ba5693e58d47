"""Fused KD loss (Pallas TPU): CE + KL straight from hidden states.

The distillation server's hot spot: with V up to 256k, materialising
teacher + student logits for a (B, S) batch costs O(B·S·V) HBM traffic
*twice*.  This kernel streams vocab tiles through VMEM and keeps only
O(T) running statistics:

  student CE (raw logits):    m_s, l_s (online logsumexp), gold, argmax
  student KL side (z_s / τ):  m_sτ, l_sτ
  teacher  KL side (z_t / τ): m_tτ, l_tτ, U = Σ e^{z_tτ-m} z_tτ,
                              W = Σ e^{z_tτ-m} z_sτ  (cross term)

Finalisation (last vocab tile):
  CE = lse_s - z_s[label]
  KL = τ² [ (U/l_t - lse_tτ) - (W/l_t - lse_sτ) ]
     = τ² E_{p_t}[ log p_t - log p_s ]

Grid: (nT, nV); vocab tiles are the sequential innermost dimension.
Tiles: hs (Bt, Ds), ws (Ds, Bv), ht (Bt, Dt), wt (Dt, Bv) — two MXU
matmuls per step with f32 accumulation; tiles stay in their storage
dtype, so VMEM ~ 2·(Bt+Bv)·D·itemsize with double buffering.  Labels,
outputs and per-row statistics are (Bt, 1) columns.

The backward pass is a vocab-blocked jnp scan (see ops.py custom_vjp) —
mathematically the same streaming pattern, left to XLA.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1.0e30


def _softcap(z, cap):
    if cap:
        return jnp.tanh(z / cap) * cap
    return z


def _dot_f32(a, b):
    """MXU matmul with f32 accumulation, without widening whole weight
    tiles to f32 in VMEM first (a (D, Bv) f32 copy of each vocab tile
    would double the kernel's scoped-VMEM footprint)."""
    dt = jnp.promote_types(a.dtype, b.dtype)
    return jax.lax.dot(a.astype(dt), b.astype(dt),
                       preferred_element_type=jnp.float32)


def _kd_kernel(hs_ref, ws_ref, ht_ref, wt_ref, lab_ref,
               ce_ref, kl_ref, cor_ref,
               ms_scr, ls_scr, gold_scr, bmax_scr, barg_scr,
               mst_scr, lst_scr, mtt_scr, ltt_scr, u_scr, w_scr, *,
               tau: float, softcap_s: float, softcap_t: float,
               block_v: int, vocab: int, with_teacher: bool):
    # per-row statistics, labels and outputs are (Bt, 1) columns: 2-D
    # blocks whose lane dim spans the whole (1-wide) array, a layout
    # Mosaic and XLA agree on (1-D (Bt,) blocks do not)
    vi = pl.program_id(1)
    nv = pl.num_programs(1)

    @pl.when(vi == 0)
    def _init():
        ms_scr[...] = jnp.full_like(ms_scr, NEG_INF)
        ls_scr[...] = jnp.zeros_like(ls_scr)
        gold_scr[...] = jnp.zeros_like(gold_scr)
        bmax_scr[...] = jnp.full_like(bmax_scr, NEG_INF)
        barg_scr[...] = jnp.zeros_like(barg_scr)
        mst_scr[...] = jnp.full_like(mst_scr, NEG_INF)
        lst_scr[...] = jnp.zeros_like(lst_scr)
        mtt_scr[...] = jnp.full_like(mtt_scr, NEG_INF)
        ltt_scr[...] = jnp.zeros_like(ltt_scr)
        u_scr[...] = jnp.zeros_like(u_scr)
        w_scr[...] = jnp.zeros_like(w_scr)

    def row_max(z):
        return jnp.max(z, axis=-1, keepdims=True)

    def row_sum(z):
        return jnp.sum(z, axis=-1, keepdims=True)

    zs = _softcap(_dot_f32(hs_ref[...], ws_ref[...]), softcap_s)  # (Bt, Bv)
    v0 = vi * block_v
    vids = v0 + jax.lax.broadcasted_iota(jnp.int32, zs.shape, 1)
    valid = vids < vocab
    zs = jnp.where(valid, zs, NEG_INF)

    # ---- student raw-logit statistics (CE + accuracy) -------------------
    m_prev = ms_scr[...]
    m_new = jnp.maximum(m_prev, row_max(zs))
    ls_scr[...] = ls_scr[...] * jnp.exp(m_prev - m_new) + \
        row_sum(jnp.where(valid, jnp.exp(zs - m_new), 0.0))
    ms_scr[...] = m_new
    hit = vids == lab_ref[...]
    gold_scr[...] += row_sum(jnp.where(hit, zs, 0.0))
    blk_max = row_max(zs)
    # first column attaining the block max (argmax's tie rule)
    blk_arg = jnp.min(jnp.where(zs == blk_max, vids, jnp.iinfo(jnp.int32).max),
                      axis=-1, keepdims=True)
    better = blk_max > bmax_scr[...]
    barg_scr[...] = jnp.where(better, blk_arg, barg_scr[...])
    bmax_scr[...] = jnp.where(better, blk_max, bmax_scr[...])

    if with_teacher:
        zt = _softcap(_dot_f32(ht_ref[...], wt_ref[...]), softcap_t)
        zt = jnp.where(valid, zt, NEG_INF)
        zs_t = zs / tau
        zt_t = zt / tau
        # student temperature-side lse
        m_prev = mst_scr[...]
        m_new = jnp.maximum(m_prev, row_max(zs_t))
        lst_scr[...] = lst_scr[...] * jnp.exp(m_prev - m_new) + row_sum(
            jnp.where(valid, jnp.exp(zs_t - m_new), 0.0))
        mst_scr[...] = m_new
        # teacher-side online stats (lse + U + cross W)
        m_prev = mtt_scr[...]
        m_new = jnp.maximum(m_prev, row_max(zt_t))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.where(valid, jnp.exp(zt_t - m_new), 0.0)
        ltt_scr[...] = ltt_scr[...] * corr + row_sum(p)
        u_scr[...] = u_scr[...] * corr + row_sum(
            p * jnp.where(valid, zt_t, 0.0))
        w_scr[...] = w_scr[...] * corr + row_sum(
            p * jnp.where(valid, zs_t, 0.0))
        mtt_scr[...] = m_new

    @pl.when(vi == nv - 1)
    def _fin():
        lse_s = ms_scr[...] + jnp.log(jnp.maximum(ls_scr[...], 1e-30))
        ce_ref[...] = (lse_s - gold_scr[...]).astype(ce_ref.dtype)
        cor_ref[...] = (barg_scr[...] == lab_ref[...]).astype(cor_ref.dtype)
        if with_teacher:
            lse_st = mst_scr[...] + jnp.log(jnp.maximum(lst_scr[...], 1e-30))
            lse_tt = mtt_scr[...] + jnp.log(jnp.maximum(ltt_scr[...], 1e-30))
            lt = jnp.maximum(ltt_scr[...], 1e-30)
            ez_t = u_scr[...] / lt
            ez_s = w_scr[...] / lt
            kl = (tau ** 2) * ((ez_t - lse_tt) - (ez_s - lse_st))
            kl_ref[...] = kl.astype(kl_ref.dtype)
        else:
            kl_ref[...] = jnp.zeros_like(kl_ref)


def kd_loss_fwd(hs, ws, ht, wt, labels, *, tau: float, softcap_s: float,
                softcap_t: float, block_t: int = 128, block_v: int = 512,
                interpret: bool = False):
    """hs: (T, Ds), ws: (Ds, V), ht: (T, Dt) | None, wt: (Dt, V) | None,
    labels: (T,) -> (ce (T,), kl (T,), correct (T,))."""
    T, Ds = hs.shape
    V = ws.shape[1]
    with_teacher = ht is not None
    if not with_teacher:  # dummies keep the pallas signature uniform
        ht = jnp.zeros((T, 1), hs.dtype)
        wt = jnp.zeros((1, V), hs.dtype)
    Dt = ht.shape[1]
    bt = min(block_t, T)
    bv = min(block_v, V)
    pad_t = (-T) % bt
    pad_v = (-V) % bv
    if pad_t:
        hs = jnp.pad(hs, ((0, pad_t), (0, 0)))
        ht = jnp.pad(ht, ((0, pad_t), (0, 0)))
        labels = jnp.pad(labels, (0, pad_t))
    if pad_v:
        ws = jnp.pad(ws, ((0, 0), (0, pad_v)))
        wt = jnp.pad(wt, ((0, 0), (0, pad_v)))
    nt = hs.shape[0] // bt
    nv = ws.shape[1] // bv

    kern = functools.partial(
        _kd_kernel, tau=tau, softcap_s=softcap_s, softcap_t=softcap_t,
        block_v=bv, vocab=V, with_teacher=with_teacher)
    col = (bt, 1)
    scr = [pltpu.VMEM(col, jnp.float32) for _ in range(4)]
    scr += [pltpu.VMEM(col, jnp.int32)]
    scr += [pltpu.VMEM(col, jnp.float32) for _ in range(6)]
    ce, kl, cor = pl.pallas_call(
        kern,
        grid=(nt, nv),
        in_specs=[
            pl.BlockSpec((bt, Ds), lambda t, v: (t, 0)),
            pl.BlockSpec((Ds, bv), lambda t, v: (0, v)),
            pl.BlockSpec((bt, Dt), lambda t, v: (t, 0)),
            pl.BlockSpec((Dt, bv), lambda t, v: (0, v)),
            pl.BlockSpec(col, lambda t, v: (t, 0)),
        ],
        out_specs=[pl.BlockSpec(col, lambda t, v: (t, 0))] * 3,
        out_shape=[jax.ShapeDtypeStruct((nt * bt, 1), jnp.float32)] * 3,
        scratch_shapes=scr,
        interpret=interpret,
    )(hs, ws, ht, wt, labels.astype(jnp.int32)[:, None])
    return ce[:T, 0], kl[:T, 0], cor[:T, 0]
