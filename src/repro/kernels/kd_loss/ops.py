"""Public wrappers with custom VJP.

Forward: the fused Pallas kernel (interpret=True on CPU).
Backward: the same vocab-streaming pattern expressed as a jnp scan over
vocab blocks (two passes: lse statistics, then gradient tiles) — XLA
fuses it tile-by-tile, so the (T, V) logits still never hit HBM whole.

  d CE/d z_s = softmax(z_s) - onehot(label)
  d KL/d z_s = τ · (softmax(z_s/τ) - softmax(z_t/τ))

The teacher side is stop-gradient by construction (no cotangents for
ht / wt) — matching Eq. 10, where the teacher is frozen.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.kd_loss.kernel import kd_loss_fwd


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


def _softcap_and_grad(z, cap):
    if not cap:
        return z, jnp.ones_like(z)
    t = jnp.tanh(z / cap)
    return t * cap, 1.0 - t * t


def _lse_stats(h, w, *, softcap, vocab, block_v, tau: float = 1.0):
    """Streaming logsumexp over vocab blocks of ``w`` (D, Vp), pad-masked.
    Returns (m, l)."""
    T = h.shape[0]
    m = jnp.full((T,), -1e30, jnp.float32)
    l = jnp.zeros((T,), jnp.float32)

    def body(carry, vi):
        m, l = carry
        z, _ = _softcap_and_grad(h @ _vocab_block(w, vi, block_v), softcap)
        z = z / tau
        vids = vi * block_v + jnp.arange(z.shape[1])
        z = jnp.where((vids < vocab)[None, :], z, -1e30)
        m_new = jnp.maximum(m, jnp.max(z, -1))
        l = l * jnp.exp(m - m_new) + jnp.sum(jnp.exp(z - m_new[:, None]), -1)
        return (m_new, l), 0

    (m, l), _ = jax.lax.scan(body, (m, l), jnp.arange(w.shape[1] // block_v))
    return m, l


def _pad_vocab(w, block_v):
    """(D, V) -> (D, Vp) with Vp a multiple of ``block_v``."""
    pad = (-w.shape[1]) % block_v
    return jnp.pad(w, ((0, 0), (0, pad))) if pad else w


def _vocab_block(w, vi, block_v):
    """Vocab tile ``vi`` of a padded (D, Vp) head, widened to f32 one tile
    at a time so the whole head never exists in f32."""
    return jax.lax.dynamic_slice_in_dim(w, vi * block_v, block_v,
                                        axis=1).astype(jnp.float32)


def _put_block(dw, dwb, vi, block_v):
    """Write one vocab tile of the head gradient into the (D, Vp) carry."""
    return jax.lax.dynamic_update_slice_in_dim(dw, dwb.astype(dw.dtype),
                                               vi * block_v, axis=1)


# ---------------------------------------------------------------------------
# CE only
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _ce(hs, ws, labels, softcap, block_v, interpret):
    ce, _, cor = kd_loss_fwd(hs, ws, None, None, labels, tau=1.0,
                             softcap_s=softcap, softcap_t=0.0,
                             block_v=block_v, interpret=interpret)
    return ce, cor


def _ce_fwd(hs, ws, labels, softcap, block_v, interpret):
    out = _ce(hs, ws, labels, softcap, block_v, interpret)
    return out, (hs, ws, labels)


def _ce_bwd(softcap, block_v, interpret, res, cots):
    hs, ws, labels = res
    dce = cots[0]  # (T,)
    hsf = hs.astype(jnp.float32)
    V = ws.shape[1]
    wp = _pad_vocab(ws, block_v)
    m, l = _lse_stats(hsf, wp, softcap=softcap, vocab=V, block_v=block_v)

    def body(carry, vi):
        dhs, dws = carry
        wb = _vocab_block(wp, vi, block_v)
        z_raw = hsf @ wb
        z, dz_cap = _softcap_and_grad(z_raw, softcap)
        p = jnp.exp(z - m[:, None]) / l[:, None]
        v0 = vi * block_v
        vids = v0 + jnp.arange(z.shape[1])
        onehot = (vids[None, :] == labels[:, None]).astype(jnp.float32)
        valid = (vids < V).astype(jnp.float32)[None, :]
        dz = (p - onehot) * dce[:, None] * dz_cap * valid
        dhs = dhs + dz @ wb.T
        return (dhs, _put_block(dws, hsf.T @ dz, vi, block_v)), 0

    (dhs, dws), _ = jax.lax.scan(
        body, (jnp.zeros_like(hsf), jnp.zeros_like(wp)),
        jnp.arange(wp.shape[1] // block_v))
    return dhs.astype(hs.dtype), dws[:, :V], None


_ce.defvjp(_ce_fwd, _ce_bwd)


def ce_from_hidden(hh, w, labels, *, softcap: float = 0.0,
                   block_v: int = 512, interpret: bool | None = None):
    """hh: (..., D), labels: (...) -> (nll (...), correct (...))."""
    if interpret is None:
        interpret = _on_cpu()
    shape = labels.shape
    hs = hh.reshape(-1, hh.shape[-1])
    ce, cor = _ce(hs, w, labels.reshape(-1), softcap, block_v, interpret)
    return ce.reshape(shape), cor.reshape(shape)


# ---------------------------------------------------------------------------
# CE + KL
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _ce_kl(hs, ws, ht, wt, labels, tau, softcap_s, softcap_t, block_v,
           interpret):
    return kd_loss_fwd(hs, ws, ht, wt, labels, tau=tau, softcap_s=softcap_s,
                       softcap_t=softcap_t, block_v=block_v,
                       interpret=interpret)


def _ce_kl_fwd(hs, ws, ht, wt, labels, tau, softcap_s, softcap_t, block_v,
               interpret):
    out = _ce_kl(hs, ws, ht, wt, labels, tau, softcap_s, softcap_t, block_v,
                 interpret)
    return out, (hs, ws, ht, wt, labels)


def _ce_kl_bwd(tau, softcap_s, softcap_t, block_v, interpret, res, cots):
    hs, ws, ht, wt, labels = res
    dce, dkl = cots[0], cots[1]
    hsf, htf = hs.astype(jnp.float32), ht.astype(jnp.float32)
    wsp, wtp = _pad_vocab(ws, block_v), _pad_vocab(wt, block_v)
    V = ws.shape[1]

    # pass 1: statistics (pad-masked)
    m_s, l_s = _lse_stats(hsf, wsp, softcap=softcap_s, vocab=V,
                          block_v=block_v)
    m_st, l_st = _lse_stats(hsf, wsp, softcap=softcap_s, vocab=V,
                            block_v=block_v, tau=tau)
    m_tt, l_tt = _lse_stats(htf, wtp, softcap=softcap_t, vocab=V,
                            block_v=block_v, tau=tau)

    # pass 2: gradient tiles
    def body(carry, vi):
        dhs, dws = carry
        wsb = _vocab_block(wsp, vi, block_v)
        wtb = _vocab_block(wtp, vi, block_v)
        zs_raw = hsf @ wsb
        zs, dcap_s = _softcap_and_grad(zs_raw, softcap_s)
        zt, _ = _softcap_and_grad(htf @ wtb, softcap_t)
        p_raw = jnp.exp(zs - m_s[:, None]) / l_s[:, None]
        p_st = jnp.exp(zs / tau - m_st[:, None]) / l_st[:, None]
        p_tt = jnp.exp(zt / tau - m_tt[:, None]) / l_tt[:, None]
        v0 = vi * block_v
        vids = v0 + jnp.arange(zs.shape[1])
        onehot = (vids[None, :] == labels[:, None]).astype(jnp.float32)
        valid = (vids < V).astype(jnp.float32)[None, :]
        dz = ((p_raw - onehot) * dce[:, None]
              + tau * (p_st - p_tt) * dkl[:, None]) * dcap_s * valid
        dhs = dhs + dz @ wsb.T
        return (dhs, _put_block(dws, hsf.T @ dz, vi, block_v)), 0

    (dhs, dws), _ = jax.lax.scan(
        body, (jnp.zeros_like(hsf), jnp.zeros_like(wsp)),
        jnp.arange(wsp.shape[1] // block_v))
    # teacher is frozen (Eq. 10): zero cotangents
    return (dhs.astype(hs.dtype), dws[:, :V],
            jnp.zeros_like(ht), jnp.zeros_like(wt), None)


_ce_kl.defvjp(_ce_kl_fwd, _ce_kl_bwd)


def ce_kl_from_hidden(hh_s, w_s, hh_t, w_t, labels, *, tau: float = 1.0,
                      softcap_s: float = 0.0, softcap_t: float = 0.0,
                      block_v: int = 512, interpret: bool | None = None):
    """(..., Ds) student + (..., Dt) teacher hiddens -> (ce, kl, correct)."""
    if interpret is None:
        interpret = _on_cpu()
    shape = labels.shape
    ce, kl, cor = _ce_kl(hh_s.reshape(-1, hh_s.shape[-1]), w_s,
                         hh_t.reshape(-1, hh_t.shape[-1]), w_t,
                         labels.reshape(-1), tau, softcap_s, softcap_t,
                         block_v, interpret)
    return ce.reshape(shape), kl.reshape(shape), cor.reshape(shape)
