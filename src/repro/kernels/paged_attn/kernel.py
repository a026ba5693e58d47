"""Paged decode attention for TPU (Pallas, scalar-prefetched block table).

A C-token query chunk per slot attends over a block-paged KV pool
without ever gathering a contiguous per-slot cache in HBM: the per-slot
block table is a **scalar-prefetch** operand, so the k/v BlockSpec index
maps read ``bt[b, j]`` and DMA exactly the pool rows the slot owns.
C=1 is the classic decode step; C>1 serves chunked prefill and the
speculative-decode verify chunk (queries occupy the CONTIGUOUS positions
``pos[b] .. pos[b] + C - 1`` — ``pos`` is the FIRST query's position).

Grid: (B, nbt) — the innermost (table-entry) dimension is sequential on
TPU, so the online-softmax accumulators persist in VMEM scratch across
j-steps, as in a flash-attention kernel's k-dimension.  Each step DMAs
one pool block with ALL its kv heads and loops over the heads inside
the kernel: a one-head block would put a 1 on the pool's second-minor
(head) dim, which the TPU's (8, 128) tiling cannot address.

BlockSpec tiling (all VMEM; pools are viewed as (n_blocks, bl, KH*D)):
  q    : (1, KH, C*G, Dq) indexed b          — G = H // KH query heads,
                                               row r = c*G + g
  k,v  : (1, bl, KH*D*)   indexed bt[b, j]   — the paged indirection
  scale: (1, bl, KH)      indexed bt[b, j]   — quantized pools only
  out  : (1, KH, C*G, Dv) indexed b

Blocks whose first row lies beyond the LAST query's position (or
entirely left of the sliding window) are skipped with ``pl.when`` — a
slot only pays for the blocks it has actually filled, which is the whole
point of paging.  Within a visible block, per-query causal/window masks
zero the probability mass directly (a block can be visible to the chunk
but fully masked for an individual query row).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1.0e30


def _paged_kernel(bt_ref, pos_ref, q_ref, k_ref, v_ref, *rest,
                  scale: float, window: int, softcap: float,
                  block_len: int, n_q: int, group: int, quantized: bool):
    if quantized:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
    b = pl.program_id(0)
    j = pl.program_id(1)
    nj = pl.num_programs(1)
    KH, CG, Dv = acc_scr.shape
    Dq = q_ref.shape[-1]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    p0 = pos_ref[b]
    base = j * block_len
    # block holds at least one position in range of SOME query
    visible = base <= p0 + n_q - 1
    if window:
        visible = visible & (base + block_len - 1 > p0 - window)

    @pl.when(visible)
    def _compute():
        kpos = base + jax.lax.broadcasted_iota(jnp.int32, (CG, block_len), 1)
        qpos = p0 + jax.lax.broadcasted_iota(
            jnp.int32, (CG, block_len), 0) // group
        ok = kpos <= qpos
        if window:
            ok = ok & (kpos > qpos - window)
        for h in range(KH):
            q = q_ref[0, h].astype(jnp.float32)                  # (CG, Dq)
            k = k_ref[0, :, h * Dq:(h + 1) * Dq].astype(jnp.float32)
            v = v_ref[0, :, h * Dv:(h + 1) * Dv].astype(jnp.float32)
            if quantized:
                # dequantize the DMA'd pool rows in-register: per-(position,
                # kv-head) scales ride the same block-table indirection
                k = k * ks_ref[0, :, h:h + 1]                    # (bl, 1)
                v = v * vs_ref[0, :, h:h + 1]
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
            if softcap:
                s = jnp.tanh(s / softcap) * softcap
            s = jnp.where(ok, s, NEG_INF)

            m_prev = m_scr[h]                                    # (CG, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            # mask the probabilities, not just the scores: a query row
            # with no visible position yet has m_new == NEG_INF, and
            # exp(NEG_INF - NEG_INF) would be 1, not 0
            p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
            corr = jnp.exp(m_prev - m_new)
            l_scr[h] = l_scr[h] * corr + jnp.sum(p, axis=-1, keepdims=True)
            acc_scr[h] = acc_scr[h] * corr + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())))
            m_scr[h] = m_new

    @pl.when(j == nj - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def paged_attention_bhgd(q, k_pool, v_pool, block_table, pos, *,
                         scale: float, window: int, softcap: float,
                         interpret: bool = False, k_scale=None,
                         v_scale=None, out_dtype=None):
    """q: (B, KH, C, G, Dq); pools: (n_blocks, bl, KH, D*);
    block_table: (B, nbt) int32; pos: (B,) int32 position of the FIRST
    query (queries sit at pos .. pos + C - 1) -> (B, KH, C, G, Dv).

    ``k_scale``/``v_scale`` (n_blocks, bl, KH) float32 mark a quantized
    pool (int8/fp8 rows); they ride the same block-table indirection and
    the kernel dequantizes each DMA'd row in-register — no extra HBM
    round-trip.  ``out_dtype`` overrides the output dtype (required when
    the pool dtype is the quantized storage dtype)."""
    B, KH, C, G, Dq = q.shape
    n_blocks, bl = k_pool.shape[:2]
    Dv = v_pool.shape[-1]
    nbt = block_table.shape[1]
    quantized = k_scale is not None
    if out_dtype is None:
        out_dtype = v_pool.dtype

    kern = functools.partial(_paged_kernel, scale=scale, window=window,
                             softcap=softcap, block_len=bl, n_q=C, group=G,
                             quantized=quantized)
    in_specs = [
        pl.BlockSpec((1, KH, C * G, Dq), lambda b, j, bt, pos: (b, 0, 0, 0)),
        pl.BlockSpec((1, bl, KH * Dq), lambda b, j, bt, pos: (bt[b, j], 0, 0)),
        pl.BlockSpec((1, bl, KH * Dv), lambda b, j, bt, pos: (bt[b, j], 0, 0)),
    ]
    operands = [q.reshape(B, KH, C * G, Dq),
                k_pool.reshape(n_blocks, bl, KH * Dq),
                v_pool.reshape(n_blocks, bl, KH * Dv)]
    if quantized:
        in_specs += [
            pl.BlockSpec((1, bl, KH), lambda b, j, bt, pos: (bt[b, j], 0, 0)),
        ] * 2
        operands += [k_scale.astype(jnp.float32),
                     v_scale.astype(jnp.float32)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, nbt),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, KH, C * G, Dv),
                               lambda b, j, bt, pos: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((KH, C * G, 1), jnp.float32),
            pltpu.VMEM((KH, C * G, 1), jnp.float32),
            pltpu.VMEM((KH, C * G, Dv), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KH, C * G, Dv), out_dtype),
        interpret=interpret,
    )(block_table.astype(jnp.int32), pos.astype(jnp.int32), *operands)
    return out.reshape(B, KH, C, G, Dv)
