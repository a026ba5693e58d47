"""Core neural layers: norms, RoPE, attention (GQA + MLA), MLPs.

Everything is a pure function over explicit parameter pytrees.  Attention
ships three execution paths:

* JAX's Pallas splash kernel (``splash_attention``), a fused causal
  flash core with a Pallas flash backward, which training / prefill
  take on one TPU device where the call allows (``attention_core_path``);
* a chunked online-softmax ("flash-style") jnp implementation — the XLA
  path everywhere else, without ever materialising the (Sq, Sk) score
  matrix;
* a Pallas TPU kernel (``repro.kernels.flash_attention``) selected with
  ``cfg.use_pallas`` (validated under ``interpret=True`` on CPU).

Decode (single-token query vs. a long cache) uses a direct einsum — it is
O(S) per step and memory-light.
"""
from __future__ import annotations

import logging
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import quant
from repro.models.config import ModelConfig
from repro.utils import scopes

NEG_INF = -1.0e30


# ---------------------------------------------------------------------------
# initialisers
# ---------------------------------------------------------------------------

def dense_init(key, shape, in_axis: int = 0, dtype=jnp.float32):
    """Truncated-normal fan-in init (LeCun-style), stored in model dtype."""
    fan_in = shape[in_axis] if isinstance(in_axis, int) else 1
    std = 1.0 / math.sqrt(max(fan_in, 1))
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32) * std).astype(dtype)


def embed_init(key, shape, dtype=jnp.float32):
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def init_norm(cfg: ModelConfig, d: int, dtype):
    if cfg.norm_type == "layernorm":
        return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}
    return {"scale": jnp.ones((d,), dtype)}


@scopes.scoped(scopes.NORM)
def apply_norm(p, x, eps: float):
    xf = x.astype(jnp.float32)
    if "bias" in p:  # layernorm
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
        y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    else:  # rmsnorm
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(ms + eps) * p["scale"].astype(jnp.float32)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# rotary position embedding
# ---------------------------------------------------------------------------

def apply_rope(x, positions, theta: float):
    """x: (..., S, H, D) with D even; positions: broadcastable to (..., S)."""
    d = x.shape[-1]
    half = d // 2
    freqs = jnp.exp(-jnp.arange(0, half, dtype=jnp.float32) * (math.log(theta) / half))
    angles = positions.astype(jnp.float32)[..., None] * freqs  # (..., S, half)
    cos = jnp.cos(angles)[..., None, :]  # (..., S, 1, half)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(positions, d: int):
    half = d // 2
    freqs = jnp.exp(-jnp.arange(half, dtype=jnp.float32) * (math.log(10000.0) / max(half - 1, 1)))
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# masking helper
# ---------------------------------------------------------------------------

def _mask_bias(q_pos, k_pos, *, causal: bool, window: int):
    """Additive bias (..., Sq, Sk) from absolute positions. k_pos < 0 = pad."""
    qp = q_pos[..., :, None]
    kp = k_pos[..., None, :]
    ok = kp >= 0
    if causal:
        ok = ok & (kp <= qp)
    if window:
        ok = ok & (kp > qp - window)
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def _softcap(s, cap: float):
    if cap and cap > 0.0:
        return jnp.tanh(s / cap) * cap
    return s


# ---------------------------------------------------------------------------
# chunked online-softmax attention (XLA flash path)
# ---------------------------------------------------------------------------

def chunked_attention(
    q, k, v, q_pos, k_pos, *,
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    scale: Optional[float] = None,
    q_chunk: int = 1024,
    k_chunk: int = 1024,
    skip_masked_chunks: bool = False,
    unroll: bool = False,
    remat_chunks: bool = False,
):
    """q: (B,Sq,H,Dq)  k: (B,Sk,KH,Dq)  v: (B,Sk,KH,Dv)  ->  (B,Sq,H,Dv).

    Never materialises (Sq, Sk); accumulates in f32 with a running
    max/denominator (online softmax).  With ``skip_masked_chunks`` the
    (statically known) fully-masked chunk pairs — above the causal
    diagonal, or outside the sliding window — are skipped entirely, which
    halves causal-prefill FLOPs and makes local-attention cost O(S·W).
    """
    B, Sq, H, Dq = q.shape
    _, Sk, KH, _ = k.shape
    Dv = v.shape[-1]
    G = H // KH
    if scale is None:
        scale = 1.0 / math.sqrt(Dq)

    qc = min(q_chunk, Sq)
    kc = min(k_chunk, Sk)
    # pad to multiples
    Sq_p = -(-Sq // qc) * qc
    Sk_p = -(-Sk // kc) * kc
    q = jnp.pad(q, ((0, 0), (0, Sq_p - Sq), (0, 0), (0, 0)))
    k = jnp.pad(k, ((0, 0), (0, Sk_p - Sk), (0, 0), (0, 0)))
    v = jnp.pad(v, ((0, 0), (0, Sk_p - Sk), (0, 0), (0, 0)))
    q_pos = jnp.pad(q_pos, ((0, 0), (0, Sq_p - Sq)), constant_values=0)
    k_pos = jnp.pad(k_pos, ((0, 0), (0, Sk_p - Sk)), constant_values=-1)

    nq, nk = Sq_p // qc, Sk_p // kc
    # (B, KH, G, nq, qc, D)
    qr = q.reshape(B, nq, qc, KH, G, Dq).transpose(1, 0, 3, 4, 2, 5)
    kr = k.reshape(B, nk, kc, KH, Dq).transpose(1, 0, 3, 2, 4)
    vr = v.reshape(B, nk, kc, KH, Dv).transpose(1, 0, 3, 2, 4)
    qp = q_pos.reshape(B, nq, qc).transpose(1, 0, 2)
    kp = k_pos.reshape(B, nk, kc).transpose(1, 0, 2)

    def kv_step_inner(carry, inputs, q_blk, qp_blk):
        m, l, o = carry
        k_blk, v_blk, kp_blk = inputs
        s = jnp.einsum("bhgqd,bhkd->bhgqk", q_blk.astype(jnp.float32),
                       k_blk.astype(jnp.float32)) * scale
        s = _softcap(s, softcap)
        bias = _mask_bias(qp_blk, kp_blk, causal=causal, window=window)
        s = s + bias[:, None, None]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        o_new = o * corr[..., None] + jnp.einsum(
            "bhgqk,bhkd->bhgqd", p, v_blk.astype(jnp.float32))
        return (m_new, l_new, o_new), None

    if remat_chunks:
        # recompute s/p during backward: the saved residuals per kv-chunk
        # drop from O(qc*kc) score tensors to the O(qc) m/l/o carries
        kv_step = jax.checkpoint(
            lambda c, i, qb, qpb: kv_step_inner(c, i, qb, qpb),
            static_argnums=())
    else:
        kv_step = kv_step_inner

    def q_step(q_blk, qp_blk, qi):
        m0 = jnp.full((B, KH, G, qc), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KH, G, qc), jnp.float32)
        o0 = jnp.zeros((B, KH, G, qc, Dv), jnp.float32)
        if skip_masked_chunks:
            # static chunk-level visibility: q rows of chunk qi span
            # [qi*qc, qi*qc+qc); k chunk ki spans [ki*kc, ki*kc+kc).
            carry = (m0, l0, o0)
            for ki in range(nk):
                if causal and ki * kc > qi * qc + qc - 1:
                    continue  # entirely above the causal diagonal
                if window and (ki * kc + kc - 1) <= (qi * qc - window):
                    continue  # entirely left of every query's window
                carry, _ = kv_step(carry, (kr[ki], vr[ki], kp[ki]), q_blk, qp_blk)
            m, l, o = carry
        else:
            (m, l, o), _ = jax.lax.scan(
                lambda c, x: kv_step(c, x, q_blk, qp_blk), (m0, l0, o0),
                (kr, vr, kp), unroll=nk if unroll else 1)
        return o / jnp.maximum(l, 1e-30)[..., None]

    if skip_masked_chunks or unroll:
        outs = [q_step(qr[qi], qp[qi], qi) for qi in range(nq)]
        out = jnp.stack(outs, axis=0)
    else:
        out = jax.lax.map(lambda args: q_step(args[0], args[1], 0), (qr, qp))
    # (nq, B, KH, G, qc, Dv) -> (B, Sq, H, Dv)
    out = out.transpose(1, 0, 4, 2, 3, 5).reshape(B, Sq_p, H, Dv)
    return out[:, :Sq].astype(v.dtype)


# ---------------------------------------------------------------------------
# fused causal flash attention (Pallas splash kernel, TPU)
# ---------------------------------------------------------------------------

# Tried largest first: the first that divides S and keeps a block of q,
# k or v rows within _SPLASH_TILE_BYTES is the q and kv block of every
# splash kernel.  1024 was the fastest on a v5e at both benchmark cells'
# shapes (bf16; qk 128 and qk 192 / v 128); the dq kernel runs out of
# VMEM above the tile limit (float32 at 1024 x 192, for one).
_SPLASH_BLOCKS = (1024, 512, 256, 128)
_SPLASH_TILE_BYTES = 1024 * 256 * 2


def splash_block(S: int, width: int, itemsize: int) -> int:
    """The splash block for S rows of ``width`` values of ``itemsize``
    bytes; S a multiple of 128."""
    return next(b for b in _SPLASH_BLOCKS if S % b == 0
                and b * width * itemsize <= _SPLASH_TILE_BYTES)


def splash_attention(q, k, v, *, block: Optional[int] = None,
                     interpret: Optional[bool] = None):
    """Causal attention by index through JAX's Pallas TPU splash kernel.

    q: (B,S,H,Dq)  k: (B,S,KH,Dq)  v: (B,S,KH,Dv)  ->  (B,S,H,Dv), with
    S a multiple of ``block`` (default ``splash_block``).  Query i sees
    keys 0..i.  Each score tile lives only in VMEM, the softmax
    statistics stay float32, and blocks wholly above the diagonal are
    never loaded, in the forward or in the backward's dk/dv and dq
    kernels, which read only the output and the log-sum-exp the forward
    saved.  (The kernel's fused backward measured 12-16% faster on a v5e
    at the benchmark cells' shapes, but keeps a float32 (S/block, H, S,
    Dq) buffer of partial dq: quadratic in S.)  q, k and v enter the MXU
    in their own dtype with float32 accumulation; the kernel takes no
    softmax scale, so q is scaled by 1/sqrt(Dq) in float32 and rounded
    back to its dtype.  ``interpret`` defaults to running the kernel in
    interpret mode on the CPU.
    """
    from jax.experimental.pallas.ops.tpu import splash_attention as sa

    B, S, H, Dq = q.shape
    if block is None:
        block = splash_block(S, max(Dq, v.shape[-1]), q.dtype.itemsize)
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    sizes = sa.BlockSizes(
        block_q=block, block_kv=block, block_kv_compute=block,
        block_q_dkv=block, block_kv_dkv=block, block_kv_dkv_compute=block,
        block_q_dq=block, block_kv_dq=block)
    mask = sa.MultiHeadMask([sa.CausalMask((S, S))] * H)
    kernel = sa.make_splash_mha(mask, block_sizes=sizes, head_shards=1,
                                q_seq_shards=1, interpret=interpret)
    q = (q.astype(jnp.float32) / math.sqrt(Dq)).astype(q.dtype)
    out = jax.vmap(kernel)(*(jnp.swapaxes(x, 1, 2) for x in (q, k, v)))
    return jnp.swapaxes(out, 1, 2)


def _on_one_tpu() -> bool:
    return jax.default_backend() == "tpu" and jax.device_count() == 1


_CORE_LOGGED: set = set()


def attention_core_path(cfg: ModelConfig, q, v, *, causal: bool,
                        window: int, softcap: float) -> str:
    """Which core serves a full-sequence self-attention call:
    ``"splash"`` (``splash_attention``) or ``"chunked"``
    (``chunked_attention``), logged once per distinct reason.

    Splash is taken on a process with one TPU device, for a causal call
    with no sliding window and no logit softcap, S a multiple of 128,
    and ``use_pallas`` false (which keeps its own Pallas core).  One
    device: XLA cannot partition a Pallas kernel called outside
    ``shard_map``, and the core is given no mesh.
    """
    B, S, H, Dq = q.shape
    if cfg.use_pallas:
        path, why = "chunked", "use_pallas"
    elif not _on_one_tpu():
        path, why = "chunked", (f"backend {jax.default_backend()}, "
                                f"{jax.device_count()} devices")
    elif not causal:
        path, why = "chunked", "not causal"
    elif window:
        path, why = "chunked", f"window {window}"
    elif softcap:
        path, why = "chunked", f"softcap {softcap}"
    elif S % 128:
        path, why = "chunked", f"S {S} not a multiple of 128"
    else:
        Dv = v.shape[-1]
        block = splash_block(S, max(Dq, Dv), q.dtype.itemsize)
        path, why = "splash", (f"causal, {B} x {S} x {H}, qk {Dq} / v {Dv}, "
                               f"blocks {block}")
    if (path, why) not in _CORE_LOGGED:
        _CORE_LOGGED.add((path, why))
        logging.getLogger(__name__).info("attention core: %s (%s)", path,
                                         why)
    return path


def full_attention_core(cfg: ModelConfig, q, k, v, positions, *,
                        causal: bool = True, window: int = 0,
                        softcap: float = 0.0):
    """The self-attention core of ``attention_full`` and ``mla_full``:
    shapes as ``chunked_attention``'s, softmax scale 1/sqrt(Dq), with
    ``positions`` (B, S) for both queries and keys.

    ``splash_attention`` masks by index where ``chunked_attention``
    masks by ``positions``; the two agree because every full-sequence
    caller in ``models/model.py`` passes ``jnp.arange(S)`` for each row,
    and the one caller that does not (the MTP draft) passes S = 1, which
    ``attention_core_path`` keeps on the chunked core.
    """
    if attention_core_path(cfg, q, v, causal=causal, window=window,
                           softcap=softcap) == "splash":
        return splash_attention(q, k, v)
    return chunked_attention(
        q, k, v, positions, positions, causal=causal, window=window,
        softcap=softcap,
        q_chunk=cfg.attn_chunk_q, k_chunk=cfg.attn_chunk_k,
        skip_masked_chunks=cfg.attn_skip_masked_chunks,
        unroll=cfg.scan_unroll, remat_chunks=cfg.remat_attn_chunks)


def _constrain_seq(x, mesh, dim):
    """Keep a decode score tensor sharded (batch x data, cache-seq x model).

    Without this XLA (on the 16x16 mesh) prefers to ALL-GATHER the KV /
    MLA-latent cache over the "model" axis per layer — for deepseek-v3
    decode_32k that is ~260 GB of ICI traffic per step.  Constraining the
    scores keeps the einsum sequence-sharded; softmax then needs only a
    tiny max/sum all-reduce.  The batch dim must be pinned to the data
    axes at the same time, or XLA replicates the whole score computation
    per device (EXPERIMENTS.md §Perf, iterations D1/D4).
    """
    if mesh is None or "model" not in getattr(mesh, "axis_names", ()):
        return x
    from jax.sharding import NamedSharding, PartitionSpec as P
    spec = [None] * x.ndim
    spec[dim] = "model"
    daxes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    n_data = 1
    for a in daxes:
        n_data *= mesh.shape[a]
    if n_data > 1 and x.shape[0] % n_data == 0:
        spec[0] = daxes if len(daxes) > 1 else daxes[0]
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*spec)))


def decode_attention(q, k_cache, v_cache, q_pos, k_pos, *,
                     window: int = 0, softcap: float = 0.0,
                     scale: Optional[float] = None, causal: bool = True,
                     mesh=None):
    """Decode/chunk attention.  q: (B,C,H,Dq); caches: (B,S,KH,D*).

    C is 1 for single-token decode; chunked prefill attends C queries
    against the same cache view with per-query positional masking."""
    B, C, H, Dq = q.shape
    KH = k_cache.shape[2]
    G = H // KH
    if scale is None:
        scale = 1.0 / math.sqrt(Dq)
    qr = q.reshape(B, C, KH, G, Dq)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qr.astype(jnp.float32),
                   k_cache.astype(jnp.float32)) * scale
    s = _constrain_seq(s, mesh, 4)
    s = _softcap(s, softcap)
    bias = _mask_bias(q_pos, k_pos, causal=causal, window=window)
    s = s + bias[:, None, None]
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", w, v_cache.astype(jnp.float32))
    return o.reshape(B, C, H, v_cache.shape[-1]).astype(v_cache.dtype)


# ---------------------------------------------------------------------------
# GQA attention layer
# ---------------------------------------------------------------------------

def init_attention(key, cfg: ModelConfig, dtype):
    D, H, KH, Dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (D, H * Dh), 0, dtype),
        "wk": dense_init(ks[1], (D, KH * Dh), 0, dtype),
        "wv": dense_init(ks[2], (D, KH * Dh), 0, dtype),
        "wo": dense_init(ks[3], (H * Dh, D), 0, dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = {"scale": jnp.ones((Dh,), dtype)}
        p["k_norm"] = {"scale": jnp.ones((Dh,), dtype)}
    return p


def attention_qkv(p, cfg: ModelConfig, x, positions):
    B, S, D = x.shape
    H, KH, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    q = (x @ p["wq"]).reshape(B, S, H, Dh)
    k = (x @ p["wk"]).reshape(B, S, KH, Dh)
    v = (x @ p["wv"]).reshape(B, S, KH, Dh)
    if cfg.qk_norm:
        q = apply_norm(p["q_norm"], q, cfg.norm_eps)
        k = apply_norm(p["k_norm"], k, cfg.norm_eps)
    if cfg.pos_embedding == "rope":
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


@scopes.scoped(scopes.ATTENTION)
def attention_full(p, cfg: ModelConfig, x, positions, *, window: int,
                   causal: bool = True):
    """Full-sequence (train / prefill) attention. Returns (out, (k, v))."""
    q, k, v = attention_qkv(p, cfg, x, positions)
    if cfg.use_pallas:
        from repro.kernels.flash_attention import ops as fa_ops
        out = fa_ops.flash_attention(
            q, k, v, positions, positions, causal=causal, window=window,
            softcap=cfg.attn_logit_softcap)
    else:
        out = full_attention_core(cfg, q, k, v, positions, causal=causal,
                                  window=window,
                                  softcap=cfg.attn_logit_softcap)
    B, S = x.shape[:2]
    return out.reshape(B, S, -1) @ p["wo"], (k, v)


def paged_insert(pool, block_table, pos, entry):
    """Scatter C tokens' cache entries into a block pool.

    pool: (n_blocks, block_len, ...); entry (B, C, ...) at logical
    positions ``pos`` (B, C): position p lives in pool row
    ``block_table[b, p // block_len]`` at offset ``p % block_len``.  The
    engine guarantees the write-frontier blocks of every live slot are
    uniquely owned (shared prefix blocks sit strictly below the
    frontier; a shared-prefix chunked prefill passes a write table whose
    shared rows point at the trash block) and points dead slots at the
    sacrificial trash block 0.  ``pos // block_len`` must stay inside
    the table width — table gathers clamp out-of-bounds, so an
    undersized table would silently alias the last entry's block.
    """
    bl = pool.shape[1]
    bidx = jnp.arange(pos.shape[0])
    blk = block_table[bidx[:, None], pos // bl]          # (B, C)
    return pool.at[blk, pos % bl].set(entry.astype(pool.dtype))


def paged_gather(pool, block_table):
    """Assemble per-slot contiguous views from a block pool.

    (n_blocks, block_len, ...) gathered through (B, nbt) block tables →
    (B, nbt*block_len, ...): gathered index j IS logical position j.
    """
    B = block_table.shape[0]
    return pool[block_table].reshape((B, -1) + pool.shape[2:])


_PAGED_PATH_LOGGED: set = set()


def paged_read_path(cfg: ModelConfig, C: int, attn: str = "gqa") -> str:
    """Which paged-attention read path serves this call: ``"pallas"``
    (the scalar-prefetched block-table kernel) or ``"gather"`` (the
    block-table gather reference).

    The fallback selection is explicit — and logged once per distinct
    reason — so sharded benches can report which path actually ran: the
    Pallas kernel covers GQA at any chunk width (C=1 decode, C>1
    chunked-prefill and speculative-verify chunks — the former gather
    fallback for C>1 is retired), while MLA's latent cache attends
    through the absorbed-matrix gather path.
    """
    if attn == "mla":
        path, why = "gather", "MLA latent layout"
    elif not cfg.use_pallas:
        path, why = "gather", "use_pallas=False"
    elif C != 1:
        path, why = "pallas", f"multi-query chunk (C={C})"
    else:
        path, why = "pallas", "single-query decode"
    if (path, why) not in _PAGED_PATH_LOGGED:
        _PAGED_PATH_LOGGED.add((path, why))
        logging.getLogger(__name__).info(
            "paged_attn read path: %s (%s)", path, why)
    return path


@scopes.scoped(scopes.ATTENTION)
def attention_decode(p, cfg: ModelConfig, x, pos, cache, *,
                     window: int, mesh=None, block_table=None,
                     write_table=None):
    """Decode / chunked-prefill attention.  x: (B,C,D), pos: (B,C).

    C=1 is the single-token decode step; C>1 is one chunked-prefill
    chunk: all C k/v entries are written into the cache first, then the
    C queries attend over the updated view with per-query causal (and
    window) masking — in-chunk causality falls out of the position mask.

    ``cache`` is the layer's cache-entry dict: ``{"k", "v"}`` plus
    ``{"k_scale", "v_scale"}`` under a quantized ``CachePolicy``
    (int8/fp8 data with per-(position, kv-head) float32 scales — see
    ``repro.models.quant``).  Quantized entries are quantized at write
    time, so the same token content always produces the same block
    bytes; reads dequantize the attended view (the Pallas paged path
    fuses the dequant into the kernel).

    Contiguous (``block_table=None``): caches (B,Smax,KH,Dh); inserts
    this chunk's k/v at ``pos`` (per-batch scatter; positions beyond
    Smax — bucket padding — are dropped by the scatter) and attends over
    the updated cache.  Paged: caches are block pools (n_blocks,
    block_len,KH,Dh); inserts through ``write_table`` (defaults to
    ``block_table``; chunked admission points already-pooled shared
    prefix rows at the trash block) and attends over the gathered (or
    Pallas block-table-indexed) view.  Returns (out, new_cache_dict).
    """
    B, C = x.shape[:2]
    q, k, v = attention_qkv(p, cfg, x, pos)
    quantized = "k_scale" in cache
    cache = dict(cache)
    if quantized:
        kv_dtype = quant.kv_dtype_of_leaf(cache["k"])
        k_w, ks_w = quant.quantize(k, kv_dtype)
        v_w, vs_w = quant.quantize(v, kv_dtype)
    else:
        k_w, v_w = k, v
    if block_table is None:
        bidx = jnp.arange(B)
        idx = (bidx[:, None], pos)
        cache["k"] = cache["k"].at[idx].set(k_w.astype(cache["k"].dtype))
        cache["v"] = cache["v"].at[idx].set(v_w.astype(cache["v"].dtype))
        if quantized:
            cache["k_scale"] = cache["k_scale"].at[idx].set(ks_w)
            cache["v_scale"] = cache["v_scale"].at[idx].set(vs_w)
            kg = quant.dequantize(cache["k"], cache["k_scale"], x.dtype)
            vg = quant.dequantize(cache["v"], cache["v_scale"], x.dtype)
        else:
            kg, vg = cache["k"], cache["v"]
    else:
        wt = block_table if write_table is None else write_table
        cache["k"] = paged_insert(cache["k"], wt, pos, k_w)
        cache["v"] = paged_insert(cache["v"], wt, pos, v_w)
        if quantized:
            cache["k_scale"] = paged_insert(cache["k_scale"], wt, pos, ks_w)
            cache["v_scale"] = paged_insert(cache["v_scale"], wt, pos, vs_w)
        if paged_read_path(cfg, C) == "pallas":
            # chunk positions are consecutive per slot (decode, chunked
            # prefill, and the speculative verify chunk all are), so the
            # kernel takes the first query's position and derives the rest
            from repro.kernels.paged_attn import ops as pa_ops
            out = pa_ops.paged_decode_attention(
                q, cache["k"], cache["v"], block_table, pos[:, 0],
                window=window, softcap=cfg.attn_logit_softcap,
                k_scale=cache.get("k_scale"), v_scale=cache.get("v_scale"),
                out_dtype=x.dtype if quantized else None)
            return out.reshape(B, C, -1) @ p["wo"], cache
        kg = paged_gather(cache["k"], block_table)
        vg = paged_gather(cache["v"], block_table)
        if quantized:
            kg = quant.dequantize(
                kg, paged_gather(cache["k_scale"], block_table), x.dtype)
            vg = quant.dequantize(
                vg, paged_gather(cache["v_scale"], block_table), x.dtype)
    Smax = kg.shape[1]
    k_pos = jnp.arange(Smax)[None, :].repeat(B, 0)
    out = decode_attention(q, kg, vg, pos, k_pos,
                           window=window, softcap=cfg.attn_logit_softcap,
                           mesh=mesh)
    return out.reshape(B, C, -1) @ p["wo"], cache


# ---------------------------------------------------------------------------
# MLA — multi-head latent attention (DeepSeek-V3)
# ---------------------------------------------------------------------------

def init_mla(key, cfg: ModelConfig, dtype):
    D, H = cfg.d_model, cfg.n_heads
    r, pr = cfg.kv_lora_rank, cfg.rope_head_dim
    nd, vd = cfg.nope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 7)
    p = {
        "wkv_a": dense_init(ks[0], (D, r + pr), 0, dtype),
        "kv_norm": {"scale": jnp.ones((r,), dtype)},
        "wk_b": dense_init(ks[1], (H, r, nd), 1, dtype),
        "wv_b": dense_init(ks[2], (H, r, vd), 1, dtype),
        "wo": dense_init(ks[3], (H * vd, D), 0, dtype),
    }
    if cfg.q_lora_rank:
        p["wq_a"] = dense_init(ks[4], (D, cfg.q_lora_rank), 0, dtype)
        p["q_norm"] = {"scale": jnp.ones((cfg.q_lora_rank,), dtype)}
        p["wq_b"] = dense_init(ks[5], (cfg.q_lora_rank, H * (nd + pr)), 0, dtype)
    else:
        p["wq"] = dense_init(ks[6], (D, H * (nd + pr)), 0, dtype)
    return p


def _mla_queries(p, cfg: ModelConfig, x, positions):
    B, S, _ = x.shape
    H, nd, pr = cfg.n_heads, cfg.nope_head_dim, cfg.rope_head_dim
    if cfg.q_lora_rank:
        q = apply_norm(p["q_norm"], x @ p["wq_a"], cfg.norm_eps) @ p["wq_b"]
    else:
        q = x @ p["wq"]
    q = q.reshape(B, S, H, nd + pr)
    q_nope, q_rope = q[..., :nd], q[..., nd:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    return q_nope, q_rope


def mla_latent(p, cfg: ModelConfig, x, positions):
    """Compressed KV: returns (ckv (B,S,r), k_rope (B,S,pr))."""
    r = cfg.kv_lora_rank
    kv = x @ p["wkv_a"]
    ckv = apply_norm(p["kv_norm"], kv[..., :r], cfg.norm_eps)
    k_rope = apply_rope(kv[..., None, r:], positions, cfg.rope_theta)[..., 0, :]
    return ckv, k_rope


@scopes.scoped(scopes.ATTENTION)
def mla_full(p, cfg: ModelConfig, x, positions):
    """Training / prefill MLA.  Returns (out, (ckv, k_rope))."""
    B, S, _ = x.shape
    H, pr, vd = cfg.n_heads, cfg.rope_head_dim, cfg.v_head_dim
    q_nope, q_rope = _mla_queries(p, cfg, x, positions)
    ckv, k_rope = mla_latent(p, cfg, x, positions)
    k_nope = jnp.einsum("bsr,hrn->bshn", ckv, p["wk_b"].astype(ckv.dtype))
    v = jnp.einsum("bsr,hrv->bshv", ckv, p["wv_b"].astype(ckv.dtype))
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope[:, :, None], (B, S, H, pr))], axis=-1)
    out = full_attention_core(cfg, q, k, v, positions)
    return out.reshape(B, S, H * vd) @ p["wo"], (ckv, k_rope)


def _mla_attend(p, cfg: ModelConfig, x, pos, ckv, krope, mesh):
    """Absorbed-matrix attention over a (B, S, r)/(B, S, pr) latent view
    whose index along S is the logical position (contiguous cache, or a
    block-table gather of a paged pool).  x: (B,C,D), pos: (B,C) — C>1
    is one chunked-prefill chunk, masked causally per query."""
    B, C = x.shape[:2]
    H, nd, pr, vd = cfg.n_heads, cfg.nope_head_dim, cfg.rope_head_dim, cfg.v_head_dim
    q_nope, q_rope = _mla_queries(p, cfg, x, pos)
    # absorb W_UK into the query:  (B,C,H,nd) x (H,r,nd) -> (B,C,H,r)
    q_lat = jnp.einsum("bqhn,hrn->bqhr", q_nope, p["wk_b"].astype(q_nope.dtype))
    Smax = ckv.shape[1]
    k_pos = jnp.arange(Smax)[None, :].repeat(B, 0)
    s = (jnp.einsum("bqhr,bsr->bhqs", q_lat.astype(jnp.float32),
                    ckv.astype(jnp.float32))
         + jnp.einsum("bqhp,bsp->bhqs", q_rope.astype(jnp.float32),
                      krope.astype(jnp.float32)))
    s = _constrain_seq(s, mesh, 3)
    s = s / math.sqrt(nd + pr)
    s = s + _mask_bias(pos, k_pos, causal=True, window=0)[:, None]
    w = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("bhqs,bsr->bqhr", w, ckv.astype(jnp.float32))
    v = jnp.einsum("bqhr,hrv->bqhv", ctx, p["wv_b"].astype(jnp.float32))
    return v.reshape(B, C, H * vd).astype(x.dtype) @ p["wo"]


@scopes.scoped(scopes.ATTENTION)
def mla_decode(p, cfg: ModelConfig, x, pos, cache,
               mesh=None, block_table=None, write_table=None):
    """Absorbed-matrix MLA decode: attends directly in the latent space.

    The 576-float/token latent cache is what makes DeepSeek-V3 long-context
    decode feasible (long_500k).  ``cache`` is the layer's cache-entry
    dict: ``{"ckv", "kr"}`` plus ``{"ckv_scale", "kr_scale"}`` under a
    quantized policy (per-position scales over the latent/rope feature
    axis).  Inserts this chunk's latents (x (B,C,D) at pos (B,C); C=1 is
    plain decode), attends, and returns (out, new_cache_dict).  With
    ``block_table`` the caches are block pools and the attended view is
    the gathered one; ``write_table`` (chunked admission) diverts
    already-pooled shared prefix writes.
    """
    B = x.shape[0]
    ckv_t, krope_t = mla_latent(p, cfg, x, pos)
    quantized = "ckv_scale" in cache
    cache = dict(cache)
    if quantized:
        kv_dtype = quant.kv_dtype_of_leaf(cache["ckv"])
        ckv_w, cs_w = quant.quantize(ckv_t, kv_dtype)
        kr_w, krs_w = quant.quantize(krope_t, kv_dtype)
    else:
        ckv_w, kr_w = ckv_t, krope_t
    if block_table is None:
        bidx = jnp.arange(B)
        idx = (bidx[:, None], pos)
        cache["ckv"] = cache["ckv"].at[idx].set(ckv_w.astype(cache["ckv"].dtype))
        cache["kr"] = cache["kr"].at[idx].set(kr_w.astype(cache["kr"].dtype))
        if quantized:
            cache["ckv_scale"] = cache["ckv_scale"].at[idx].set(cs_w)
            cache["kr_scale"] = cache["kr_scale"].at[idx].set(krs_w)
            ckv_g = quant.dequantize(cache["ckv"], cache["ckv_scale"], x.dtype)
            krope_g = quant.dequantize(cache["kr"], cache["kr_scale"], x.dtype)
        else:
            ckv_g, krope_g = cache["ckv"], cache["kr"]
    else:
        wt = block_table if write_table is None else write_table
        cache["ckv"] = paged_insert(cache["ckv"], wt, pos, ckv_w)
        cache["kr"] = paged_insert(cache["kr"], wt, pos, kr_w)
        if quantized:
            cache["ckv_scale"] = paged_insert(cache["ckv_scale"], wt, pos, cs_w)
            cache["kr_scale"] = paged_insert(cache["kr_scale"], wt, pos, krs_w)
        paged_read_path(cfg, x.shape[1], attn="mla")
        ckv_g = paged_gather(cache["ckv"], block_table)
        krope_g = paged_gather(cache["kr"], block_table)
        if quantized:
            ckv_g = quant.dequantize(
                ckv_g, paged_gather(cache["ckv_scale"], block_table), x.dtype)
            krope_g = quant.dequantize(
                krope_g, paged_gather(cache["kr_scale"], block_table), x.dtype)
    out = _mla_attend(p, cfg, x, pos, ckv_g, krope_g, mesh)
    return out, cache


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def init_mlp(key, cfg: ModelConfig, d_in: int, d_hidden: int, dtype):
    ks = jax.random.split(key, 3)
    if cfg.mlp_gated:
        return {
            "wi_gate": dense_init(ks[0], (d_in, d_hidden), 0, dtype),
            "wi_up": dense_init(ks[1], (d_in, d_hidden), 0, dtype),
            "wo": dense_init(ks[2], (d_hidden, d_in), 0, dtype),
        }
    return {
        "wi": dense_init(ks[0], (d_in, d_hidden), 0, dtype),
        "wo": dense_init(ks[2], (d_hidden, d_in), 0, dtype),
    }


def _act(cfg: ModelConfig, x):
    if cfg.act == "gelu":
        return jax.nn.gelu(x, approximate=True)
    return jax.nn.silu(x)


def apply_mlp(p, cfg: ModelConfig, x):
    if "wi_gate" in p:
        h = _act(cfg, x @ p["wi_gate"]) * (x @ p["wi_up"])
    else:
        h = _act(cfg, x @ p["wi"])
    return h @ p["wo"]
