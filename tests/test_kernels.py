"""Per-kernel correctness sweeps: Pallas (interpret=True) vs ref.py oracles.

Shapes and dtypes are swept; every kernel must match its pure-jnp oracle
to tight tolerances.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.moe_dispatch.kernel import gather_scatter_add_rows
from repro.kernels.moe_dispatch.ops import (capacity_positions, token_combine,
                                            token_dispatch)
from repro.kernels.moe_dispatch.ref import gather_scatter_add_ref
from repro.kernels.moe_gemm.ops import grouped_ffn
from repro.kernels.moe_gemm.ref import grouped_ffn_bwd_ref, grouped_ffn_ref
from repro.kernels.ssd_scan.ops import ssd
from repro.kernels.ssd_scan.ref import ssd_ref
from repro.kernels.kd_loss.ops import ce_from_hidden, ce_kl_from_hidden
from repro.kernels.kd_loss.ref import ce_ref, ce_kl_ref

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# moe grouped FFN
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("E,C,D,F", [(4, 48, 32, 64), (2, 16, 16, 40),
                                     (8, 8, 64, 32)])
@pytest.mark.parametrize("act", ["silu", "gelu"])
def test_grouped_ffn_matches_ref(E, C, D, F, act):
    ks = jax.random.split(KEY, 4)
    x = jax.random.normal(ks[0], (E, C, D))
    wg = jax.random.normal(ks[1], (E, D, F)) * 0.1
    wu = jax.random.normal(ks[2], (E, D, F)) * 0.1
    wo = jax.random.normal(ks[3], (E, F, D)) * 0.1
    out = grouped_ffn(x, wg, wu, wo, act=act, block_c=16, block_f=16)
    ref = grouped_ffn_ref(x, wg, wu, wo, act=act)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("act", ["silu", "gelu"])
def test_grouped_ffn_grads_match_ref(act):
    """Regression for the headline bug: jax.grad through the Pallas
    grouped FFN used to raise; now it must match the reference backward
    in all four inputs."""
    E, C, D, F = 3, 20, 16, 24
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (E, C, D))
    wg = jax.random.normal(ks[1], (E, D, F)) * 0.1
    wu = jax.random.normal(ks[2], (E, D, F)) * 0.1
    wo = jax.random.normal(ks[3], (E, F, D)) * 0.1
    dy = jax.random.normal(ks[4], (E, C, D))
    gk = jax.grad(lambda *a: jnp.sum(grouped_ffn(
        *a, act=act, block_c=16, block_f=16) * dy), (0, 1, 2, 3))(x, wg, wu, wo)
    gr = jax.grad(lambda *a: jnp.sum(grouped_ffn_ref(*a, act=act) * dy),
                  (0, 1, 2, 3))(x, wg, wu, wo)
    gb = grouped_ffn_bwd_ref(x, wg, wu, wo, dy, act=act)
    for a, b, c in zip(gk, gr, gb):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# fused token dispatch / combine
# ---------------------------------------------------------------------------

def test_gather_scatter_add_matches_ref():
    ks = jax.random.split(KEY, 4)
    src = jax.random.normal(ks[0], (13, 8))
    si = jax.random.randint(ks[1], (21,), 0, 13)
    di = jax.random.randint(ks[2], (21,), 0, 9)
    sc = jax.random.normal(ks[3], (21,))
    out = gather_scatter_add_rows(src, si, di, sc, 9, interpret=True)
    ref = gather_scatter_add_ref(src, si, di, sc, 9)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_token_dispatch_combine_kernel_matches_xla_and_grads():
    """The Pallas permute/unpermute and the pure-XLA fallback must agree
    in value and gradient — they define the MoE drop semantics once."""
    T, D, E, k, cap = 18, 12, 4, 2, 6
    ks = jax.random.split(KEY, 3)
    xt = jax.random.normal(ks[0], (T, D))
    flat_e = jax.random.randint(ks[1], (T * k,), 0, E)
    weights = jax.nn.softmax(jax.random.normal(ks[2], (T * k,)))
    flat_tok = jnp.arange(T * k, dtype=jnp.int32) // k
    pos, keep = capacity_positions(flat_e, cap)
    assert int(jnp.max(jnp.where(keep, pos, 0))) < cap
    slot = flat_e * cap + pos

    def roundtrip(xt, weights, use_kernel):
        buf = token_dispatch(xt, flat_tok, slot, keep, E * cap,
                             use_kernel=use_kernel)
        return token_combine(buf, flat_tok, slot, keep, weights, T,
                             use_kernel=use_kernel)

    out_k = roundtrip(xt, weights, True)
    out_x = roundtrip(xt, weights, False)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_x),
                               rtol=1e-5, atol=1e-5)
    gk = jax.grad(lambda xt, w: roundtrip(xt, w, True).sum(), (0, 1))(
        xt, weights)
    gx = jax.grad(lambda xt, w: roundtrip(xt, w, False).sum(), (0, 1))(
        xt, weights)
    for a, b in zip(gk, gx):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


def test_use_pallas_moe_training_step_smoke():
    """End-to-end: one training step of an MoE model with use_pallas=True
    — expert FFN weights must receive nonzero, finite gradients."""
    from repro.models import model as M
    from repro.models.config import ModelConfig
    from repro.optim import adamw_init, adamw_update
    cfg = ModelConfig(name="moe-pallas-tiny", arch_type="moe", n_layers=1,
                      d_model=32, n_heads=2, n_kv_heads=2, head_dim=16,
                      d_ff=64, n_experts=4, top_k=2, moe_d_ff=64,
                      vocab_size=128, dtype="float32", remat=False,
                      attn_chunk_q=16, attn_chunk_k=16, loss_chunk=32,
                      use_pallas=True).validate()
    params = M.init_params(jax.random.PRNGKey(3), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(4), (2, 16), 0,
                              cfg.vocab_size)
    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, 1)}
    (loss, _), grads = jax.value_and_grad(
        lambda p: M.loss_fn(p, cfg, batch), has_aux=True)(params)
    assert bool(jnp.isfinite(loss))
    for name in ("wi_gate", "wi_up", "wo"):
        g = grads["blocks"]["sub0"]["moe"][name]
        assert bool(jnp.all(jnp.isfinite(g)))
        assert float(jnp.linalg.norm(g)) > 0, f"zero grad for expert {name}"
    opt = adamw_init(params)
    new_params, _, stats = adamw_update(grads, opt, params, lr=1e-3)
    assert bool(jnp.isfinite(stats["grad_norm"]))
    moved = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))),
                         params, new_params)
    assert max(jax.tree.leaves(moved)) > 0


# ---------------------------------------------------------------------------
# SSD scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,H,P,N,chunk", [
    (2, 100, 3, 8, 16, 32),
    (1, 64, 2, 16, 8, 16),
    (1, 37, 1, 8, 8, 64),   # S < chunk, odd length
])
def test_ssd_kernel_matches_sequential_ref(B, S, H, P, N, chunk):
    ks = jax.random.split(KEY, 5)
    xh = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bh = jax.random.normal(ks[3], (B, S, H, N)) * 0.3
    Ch = jax.random.normal(ks[4], (B, S, H, N)) * 0.3
    y_k, h_k = ssd(xh, dt, A, Bh, Ch, chunk=chunk)
    xb = xh.transpose(0, 2, 1, 3).reshape(B * H, S, P)
    y_r, h_r = ssd_ref(xb, dt.transpose(0, 2, 1).reshape(B * H, S),
                       jnp.tile(A, B),
                       Bh.transpose(0, 2, 1, 3).reshape(B * H, S, N),
                       Ch.transpose(0, 2, 1, 3).reshape(B * H, S, N))
    y_r = y_r.reshape(B, H, S, P).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(h_k.reshape(B * H, P, N)),
                               np.asarray(h_r), rtol=2e-4, atol=2e-4)


def test_ssd_kernel_carries_initial_state():
    B, S, H, P, N = 1, 32, 2, 8, 8
    ks = jax.random.split(KEY, 6)
    xh = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bh = jax.random.normal(ks[3], (B, S, H, N)) * 0.3
    Ch = jax.random.normal(ks[4], (B, S, H, N)) * 0.3
    h0 = jax.random.normal(ks[5], (B, H, P, N)) * 0.5
    y1, hf1 = ssd(xh, dt, A, Bh, Ch, chunk=8, init_state=h0)
    xb = xh.transpose(0, 2, 1, 3).reshape(B * H, S, P)
    y2, hf2 = ssd_ref(xb, dt.transpose(0, 2, 1).reshape(B * H, S),
                      jnp.tile(A, B),
                      Bh.transpose(0, 2, 1, 3).reshape(B * H, S, N),
                      Ch.transpose(0, 2, 1, 3).reshape(B * H, S, N),
                      h0=h0.reshape(B * H, P, N))
    np.testing.assert_allclose(
        np.asarray(y1),
        np.asarray(y2.reshape(B, H, S, P).transpose(0, 2, 1, 3)),
        rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# fused KD loss
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("T,Ds,Dt,V,tau,caps,capt,bv", [
    (16, 12, 8, 72, 2.0, 0.0, 0.0, 32),     # tau != 1, vocab pads
    (20, 8, 8, 96, 1.0, 30.0, 30.0, 32),    # both softcaps, no pad
    (12, 16, 8, 45, 4.0, 0.0, 50.0, 16),    # teacher-only cap, pad
])
def test_kd_loss_forward_and_grads(T, Ds, Dt, V, tau, caps, capt, bv):
    ks = jax.random.split(KEY, 5)
    hs = jax.random.normal(ks[0], (T, Ds))
    ws = jax.random.normal(ks[1], (Ds, V)) * 0.3
    ht = jax.random.normal(ks[2], (T, Dt))
    wt = jax.random.normal(ks[3], (Dt, V)) * 0.3
    lab = jax.random.randint(ks[4], (T,), 0, V)
    ce, kl, cor = ce_kl_from_hidden(hs, ws, ht, wt, lab, tau=tau,
                                    softcap_s=caps, softcap_t=capt,
                                    block_v=bv)
    ce_r, kl_r, cor_r = ce_kl_ref(hs, ws, ht, wt, lab, tau=tau,
                                  softcap_s=caps, softcap_t=capt)
    np.testing.assert_allclose(np.asarray(ce), np.asarray(ce_r),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(kl), np.asarray(kl_r),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(cor), np.asarray(cor_r))

    def loss_k(hs, ws):
        ce, kl, _ = ce_kl_from_hidden(hs, ws, ht, wt, lab, tau=tau,
                                      softcap_s=caps, softcap_t=capt,
                                      block_v=bv)
        return jnp.mean(ce) + 0.7 * jnp.mean(kl)

    def loss_r(hs, ws):
        ce, kl, _ = ce_kl_ref(hs, ws, ht, wt, lab, tau=tau,
                              softcap_s=caps, softcap_t=capt)
        return jnp.mean(ce) + 0.7 * jnp.mean(kl)

    gk = jax.grad(loss_k, argnums=(0, 1))(hs, ws)
    gr = jax.grad(loss_r, argnums=(0, 1))(hs, ws)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_ce_only_path():
    T, D, V = 30, 12, 77
    ks = jax.random.split(KEY, 3)
    hs = jax.random.normal(ks[0], (T, D))
    ws = jax.random.normal(ks[1], (D, V)) * 0.3
    lab = jax.random.randint(ks[2], (T,), 0, V)
    ce, cor = ce_from_hidden(hs, ws, lab, block_v=16)
    ce_r, cor_r = ce_ref(hs, ws, lab)
    np.testing.assert_allclose(np.asarray(ce), np.asarray(ce_r),
                               rtol=1e-5, atol=1e-5)
    g1 = jax.grad(lambda h: jnp.sum(
        ce_from_hidden(h, ws, lab, block_v=16)[0]))(hs)
    g2 = jax.grad(lambda h: jnp.sum(ce_ref(h, ws, lab)[0]))(hs)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               rtol=1e-4, atol=1e-5)


def test_model_layer_uses_pallas_consistently():
    """cfg.use_pallas=True must agree with the XLA path end-to-end."""
    from repro.configs import get_config
    from repro.models import model as M
    cfg = get_config("mamba2-1.3b", variant="reduced")
    params = M.init_params(jax.random.PRNGKey(7), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(8), (2, 32), 0,
                              cfg.vocab_size)
    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, 1)}
    l1, _ = M.loss_fn(params, cfg, batch)
    l2, _ = M.loss_fn(params, cfg.replace(use_pallas=True), batch)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-4)


# ---------------------------------------------------------------------------
# paged attention: multi-query chunks (speculative verify / chunked prefill)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,C,H,KH,D,nb,bl,nbt,window,softcap", [
    (3, 4, 8, 4, 32, 10, 4, 6, 0, 0.0),    # GQA verify chunk
    (2, 3, 4, 4, 16, 8, 8, 3, 0, 30.0),    # MHA + softcap
    (2, 5, 8, 2, 32, 12, 4, 7, 6, 0.0),    # sliding window, C > window gap
    (1, 8, 4, 1, 16, 9, 4, 6, 0, 0.0),     # MQA, chunk wider than a block
])
def test_paged_attention_multi_query_matches_ref(B, C, H, KH, D, nb, bl,
                                                 nbt, window, softcap):
    """C>1 query chunks (contiguous positions pos..pos+C-1) against the
    gather oracle: per-query causal masks inside the chunk, blocks that
    straddle the chunk's first/last query, GQA grouping."""
    from repro.kernels.paged_attn.ops import paged_decode_attention
    from repro.kernels.paged_attn.ref import paged_attention_ref
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(B, C, H, D)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(nb, bl, KH, D)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(nb, bl, KH, D)), jnp.float32)
    bt = jnp.asarray(rng.integers(0, nb, size=(B, nbt)), jnp.int32)
    # last query must stay inside the table: pos + C - 1 <= nbt*bl - 1
    pos = jnp.asarray(rng.integers(0, nbt * bl - C + 1, size=(B,)), jnp.int32)
    ref = paged_attention_ref(q, kp, vp, bt, pos, window=window,
                              softcap=softcap)
    out = paged_decode_attention(q, kp, vp, bt, pos, window=window,
                                 softcap=softcap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_paged_attention_block_boundary_straddle():
    """A chunk whose queries straddle a block boundary: the first query's
    block is fully visible, the last query's block only partially — the
    per-query masks must not leak future positions."""
    from repro.kernels.paged_attn.ops import paged_decode_attention
    from repro.kernels.paged_attn.ref import paged_attention_ref
    rng = np.random.default_rng(2)
    B, C, H, KH, D, nb, bl, nbt = 2, 4, 4, 2, 16, 8, 4, 5
    q = jnp.asarray(rng.normal(size=(B, C, H, D)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(nb, bl, KH, D)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(nb, bl, KH, D)), jnp.float32)
    bt = jnp.asarray(rng.integers(0, nb, size=(B, nbt)), jnp.int32)
    pos = jnp.asarray([bl - 2, 2 * bl - 1], jnp.int32)  # straddle two ways
    ref = paged_attention_ref(q, kp, vp, bt, pos)
    out = paged_decode_attention(q, kp, vp, bt, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_paged_read_path_multi_query_uses_pallas():
    """ISSUE 8: the C>1 gather fallback is retired — GQA chunks route
    through the kernel whenever cfg.use_pallas; MLA stays on gather."""
    from repro.configs import get_config
    from repro.models import layers
    gqa = get_config("tinyllama-1.1b", variant="reduced")
    mla = get_config("deepseek-v3-671b", variant="reduced")
    on = gqa.replace(use_pallas=True)
    assert layers.paged_read_path(on, 1) == "pallas"
    assert layers.paged_read_path(on, 4) == "pallas"
    assert layers.paged_read_path(gqa, 4) == "gather"        # use_pallas off
    assert layers.paged_read_path(mla.replace(use_pallas=True), 4,
                                  attn="mla") == "gather"    # MLA layout
