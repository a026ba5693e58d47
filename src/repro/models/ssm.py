"""Mamba-2 (SSD — state-space duality) block.  [arXiv:2405.21060]

Chunked SSD for train/prefill: within a chunk the computation is a
masked-attention-like quadratic form (MXU-friendly), across chunks a
recurrent state pass (B, H, P, N) carries the SSM state.  Decode is the
O(1)-per-token recurrence — this is what makes ``long_500k`` trivial for
SSM architectures.

The chunked scan also ships as a Pallas TPU kernel
(``repro.kernels.ssd_scan``) selected by ``cfg.use_pallas``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models import layers


def init_ssm(key, cfg: ModelConfig, dtype):
    D = cfg.d_model
    d_inner = cfg.d_inner
    H = cfg.ssm_heads
    N = cfg.ssm_state
    G = cfg.ssm_groups
    conv_dim = d_inner + 2 * G * N
    ks = jax.random.split(key, 4)
    return {
        "in_proj": layers.dense_init(
            ks[0], (D, 2 * d_inner + 2 * G * N + H), 0, dtype),
        "conv_w": layers.dense_init(ks[1], (cfg.ssm_conv, conv_dim), 0, dtype),
        "conv_b": jnp.zeros((conv_dim,), dtype),
        "A_log": jnp.zeros((H,), jnp.float32),
        "D": jnp.ones((H,), jnp.float32),
        "dt_bias": jnp.zeros((H,), jnp.float32),
        "norm": {"scale": jnp.ones((d_inner,), dtype)},
        "out_proj": layers.dense_init(ks[2], (d_inner, D), 0, dtype),
    }


def _split_proj(cfg: ModelConfig, proj):
    d_inner = cfg.d_inner
    G, N, H = cfg.ssm_groups, cfg.ssm_state, cfg.ssm_heads
    z = proj[..., :d_inner]
    xBC = proj[..., d_inner:d_inner + d_inner + 2 * G * N]
    dt = proj[..., -H:]
    return z, xBC, dt


def _causal_conv(cfg: ModelConfig, xBC, conv_w, conv_b, conv_cache=None):
    """Depthwise causal conv along S.  xBC: (B, S, C)."""
    K = cfg.ssm_conv
    if conv_cache is not None:
        xp = jnp.concatenate([conv_cache.astype(xBC.dtype), xBC], axis=1)
    else:
        xp = jnp.pad(xBC, ((0, 0), (K - 1, 0), (0, 0)))
    out = sum(xp[:, i:i + xBC.shape[1]] * conv_w[i] for i in range(K))
    return jax.nn.silu(out + conv_b)


def _expand_groups(t, H):
    """(B, ..., G, N) -> (B, ..., H, N) by repeating each group."""
    G = t.shape[-2]
    rep = H // G
    return jnp.repeat(t, rep, axis=-2)


def ssd_chunked(xh, dt, A, Bh, Ch, *, chunk: int, init_state=None,
                unroll: bool = False, compute_dtype=jnp.float32):
    """Chunked SSD scan (jnp oracle / XLA path).

    xh: (B,S,H,P)  dt: (B,S,H)  A: (H,) negative  Bh/Ch: (B,S,H,N)
    Returns (y (B,S,H,P), final_state (B,H,P,N)).
    """
    Bsz, S, H, Pd = xh.shape
    N = Bh.shape[-1]
    Q = min(chunk, S)
    pad = (-S) % Q
    if pad:
        xh = jnp.pad(xh, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bh = jnp.pad(Bh, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Ch = jnp.pad(Ch, ((0, 0), (0, pad), (0, 0), (0, 0)))
    Sp = S + pad
    nC = Sp // Q

    f32 = jnp.float32
    cd = jnp.dtype(compute_dtype)
    # chunk-major layout for the scan: (nC, B, Q, ...).  The matmul
    # operands may run in bf16 (Z3); decay/cumsum/state math stays f32.
    xh = xh.astype(cd).reshape(Bsz, nC, Q, H, Pd).transpose(1, 0, 2, 3, 4)
    dt = dt.astype(f32).reshape(Bsz, nC, Q, H).transpose(1, 0, 2, 3)
    Bh = Bh.astype(cd).reshape(Bsz, nC, Q, H, N).transpose(1, 0, 2, 3, 4)
    Ch = Ch.astype(cd).reshape(Bsz, nC, Q, H, N).transpose(1, 0, 2, 3, 4)

    causal = jnp.tril(jnp.ones((Q, Q), bool))
    h0 = (jnp.zeros((Bsz, H, Pd, N), f32) if init_state is None
          else init_state.astype(f32))

    def step(h, inp):
        x_c, dt_c, B_c, C_c = inp                         # (B,Q,H,*) per chunk
        dA = dt_c * A[None, None, :]                      # (B,Q,H) <= 0
        cum = jnp.cumsum(dA, axis=1)
        # intra-chunk quadratic form: L[q,s] = exp(cum[q]-cum[s]), s <= q
        Lq = cum[:, :, None, :] - cum[:, None, :, :]      # (B,Q,S,H)
        Lmat = jnp.where(causal[None, :, :, None], jnp.exp(Lq), 0.0)
        CB = jnp.einsum("bqhn,bshn->bqsh", C_c, B_c,
                        preferred_element_type=f32)
        y_intra = jnp.einsum("bqsh,bshp->bqhp", (CB * Lmat).astype(cd),
                             (x_c.astype(f32) * dt_c[..., None]).astype(cd),
                             preferred_element_type=f32)
        # contribution of the carried state
        y_inter = jnp.einsum("bqhn,bhpn->bqhp",
                             C_c.astype(f32) * jnp.exp(cum)[..., None], h,
                             preferred_element_type=f32)
        # chunk summary -> new state
        decay_to_end = jnp.exp(cum[:, -1:, :] - cum)      # (B,Q,H)
        s_c = jnp.einsum("bsh,bshn,bshp->bhpn", decay_to_end * dt_c,
                         B_c.astype(f32), x_c.astype(f32),
                         preferred_element_type=f32)
        h_new = h * jnp.exp(cum[:, -1])[:, :, None, None] + s_c
        return h_new, y_intra + y_inter

    h_final, ys = jax.lax.scan(step, h0, (xh, dt, Bh, Ch),
                               unroll=nC if unroll else 1)
    y = ys.transpose(1, 0, 2, 3, 4).reshape(Bsz, Sp, H, Pd)[:, :S]
    return y, h_final


def ssm_forward(p, cfg: ModelConfig, x, *, conv_cache=None, init_state=None,
                return_cache: bool = False):
    """Full-sequence Mamba-2 block.  x: (B, S, D) -> (B, S, D)."""
    B, S, D = x.shape
    H, Pd, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    proj = x @ p["in_proj"]
    z, xBC, dt = _split_proj(cfg, proj)
    xBC_conv = _causal_conv(cfg, xBC, p["conv_w"], p["conv_b"], conv_cache)
    d_inner = cfg.d_inner
    G = cfg.ssm_groups
    xs = xBC_conv[..., :d_inner].reshape(B, S, H, Pd)
    Bs = xBC_conv[..., d_inner:d_inner + G * N].reshape(B, S, G, N)
    Cs = xBC_conv[..., d_inner + G * N:].reshape(B, S, G, N)
    Bs, Cs = _expand_groups(Bs, H), _expand_groups(Cs, H)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])

    if cfg.use_pallas:
        from repro.kernels.ssd_scan import ops as ssd_ops
        y, h_final = ssd_ops.ssd(xs, dt, A, Bs, Cs, chunk=cfg.ssm_chunk,
                                 init_state=init_state)
    else:
        y, h_final = ssd_chunked(xs, dt, A, Bs, Cs, chunk=cfg.ssm_chunk,
                                 init_state=init_state,
                                 unroll=cfg.scan_unroll,
                                 compute_dtype=cfg.ssm_compute_dtype)
    y = y + xs.astype(jnp.float32) * p["D"][None, None, :, None]
    y = y.reshape(B, S, d_inner).astype(x.dtype)
    y = layers.apply_norm(p["norm"], y * jax.nn.silu(z), cfg.norm_eps)
    out = y @ p["out_proj"]
    if return_cache:
        K = cfg.ssm_conv
        if conv_cache is not None:
            # short continuation chunks: the carried tail still holds the
            # older inputs the next window needs
            tail = jnp.concatenate([conv_cache.astype(xBC.dtype), xBC],
                                   axis=1)[:, -(K - 1):]
        elif S >= K - 1:
            tail = xBC[:, -(K - 1):]
        else:
            tail = jnp.pad(xBC, ((0, 0), (K - 1 - S, 0), (0, 0)))
        return out, {"state": h_final, "conv": tail}
    return out


def ssm_prefill_chunk(p, cfg: ModelConfig, x, cache, n_valid=None):
    """One chunked-prefill chunk through a Mamba-2 block: C tokens with
    recurrent state + conv-tail carry.  x: (B, C, D), cache as in
    ``ssm_decode``.  Returns (out (B, C, D), new_cache).

    ``n_valid`` (B,) masks bucket padding at the chunk tail: positions
    ``>= n_valid`` contribute NOTHING to the carried state (their
    softplus'd dt is zeroed, so the SSD decay is exp(0)=1 and the update
    term vanishes) and the carried conv tail is sliced to end at the
    last *valid* input — unlike attention, the recurrence integrates
    every token it sees, so pads must be frozen out explicitly.
    """
    B, C, _ = x.shape
    H, Pd, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    d_inner, G, K = cfg.d_inner, cfg.ssm_groups, cfg.ssm_conv
    proj = x @ p["in_proj"]
    z, xBC, dt = _split_proj(cfg, proj)
    conv_in = jnp.concatenate([cache["conv"].astype(xBC.dtype), xBC], axis=1)
    xBC_conv = _causal_conv(cfg, xBC, p["conv_w"], p["conv_b"],
                            cache["conv"].astype(xBC.dtype))
    xs = xBC_conv[..., :d_inner].reshape(B, C, H, Pd)
    Bs = _expand_groups(
        xBC_conv[..., d_inner:d_inner + G * N].reshape(B, C, G, N), H)
    Cs = _expand_groups(
        xBC_conv[..., d_inner + G * N:].reshape(B, C, G, N), H)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    if n_valid is not None:
        valid = jnp.arange(C)[None, :] < n_valid[:, None]       # (B, C)
        dt = jnp.where(valid[..., None], dt, 0.0)
    A = -jnp.exp(p["A_log"])
    if cfg.use_pallas:
        from repro.kernels.ssd_scan import ops as ssd_ops
        y, h_final = ssd_ops.ssd(xs, dt, A, Bs, Cs, chunk=cfg.ssm_chunk,
                                 init_state=cache["state"])
    else:
        y, h_final = ssd_chunked(xs, dt, A, Bs, Cs, chunk=cfg.ssm_chunk,
                                 init_state=cache["state"],
                                 unroll=cfg.scan_unroll,
                                 compute_dtype=cfg.ssm_compute_dtype)
    y = y + xs.astype(jnp.float32) * p["D"][None, None, :, None]
    y = y.reshape(B, C, d_inner).astype(x.dtype)
    y = layers.apply_norm(p["norm"], y * jax.nn.silu(z), cfg.norm_eps)
    out = y @ p["out_proj"]
    # conv tail: the K-1 inputs preceding the valid frontier.  conv_in
    # row b covers chunk-relative positions [-(K-1), C); the tail ends at
    # n_valid, i.e. starts at conv_in index n_valid (clamped 0..C).
    if n_valid is None:
        tail = conv_in[:, -(K - 1):]
    else:
        start = jnp.clip(n_valid, 0, C)
        tail = jax.vmap(
            lambda row, s: jax.lax.dynamic_slice_in_dim(row, s, K - 1, 0)
        )(conv_in, start)
    return out, {"state": h_final, "conv": tail}


def ssm_decode(p, cfg: ModelConfig, x, cache):
    """Single-token recurrent step.  x: (B, 1, D)."""
    B = x.shape[0]
    H, Pd, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    d_inner, G = cfg.d_inner, cfg.ssm_groups
    proj = x @ p["in_proj"]
    z, xBC, dt = _split_proj(cfg, proj)
    # conv over (cache ++ this step)
    conv_in = jnp.concatenate([cache["conv"].astype(xBC.dtype), xBC], axis=1)
    K = cfg.ssm_conv
    out_c = sum(conv_in[:, i + conv_in.shape[1] - K] * p["conv_w"][i]
                for i in range(K))
    xBC_conv = jax.nn.silu(out_c + p["conv_b"])[:, None]  # (B,1,C)
    xs = xBC_conv[..., :d_inner].reshape(B, H, Pd)
    Bs = _expand_groups(xBC_conv[..., d_inner:d_inner + G * N].reshape(B, G, N), H)
    Cs = _expand_groups(xBC_conv[..., d_inner + G * N:].reshape(B, G, N), H)
    dt1 = jax.nn.softplus(dt[:, 0].astype(jnp.float32) + p["dt_bias"])  # (B,H)
    A = -jnp.exp(p["A_log"])
    h = cache["state"].astype(jnp.float32)                # (B,H,P,N)
    dec = jnp.exp(dt1 * A[None, :])                       # (B,H)
    h_new = (h * dec[:, :, None, None]
             + jnp.einsum("bh,bhn,bhp->bhpn", dt1, Bs.astype(jnp.float32),
                          xs.astype(jnp.float32)))
    y = jnp.einsum("bhn,bhpn->bhp", Cs.astype(jnp.float32), h_new)
    y = y + xs.astype(jnp.float32) * p["D"][None, :, None]
    y = y.reshape(B, 1, d_inner).astype(x.dtype)
    y = layers.apply_norm(p["norm"], y * jax.nn.silu(z), cfg.norm_eps)
    out = y @ p["out_proj"]
    new_cache = {"state": h_new, "conv": conv_in[:, -(K - 1):]}
    return out, new_cache
