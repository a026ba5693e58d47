"""The training / prefill attention core: ``chunked_attention`` against a
float32 softmax over masks, shapes and dtypes, the splash kernel in
interpret mode against both, the choice of core from backend and shape,
and the models' full-sequence callers under the splash core against the
chunked one.

No chip is needed: ``layers._on_one_tpu`` is patched where a test needs
the TPU's choice, and the kernel runs in interpret mode on the CPU.
"""
import logging
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import layers
from repro.models import model as M

# bf16 keeps 8 significant bits (relative rounding 2**-9 ~ 2e-3); the
# kernel rounds q x scale and the probabilities to bf16 and sums up to
# S of them in float32, so its outputs and gradients may differ from a
# float32 softmax by a few such roundings of the largest element.
BF16_TOL = 2e-2


def _softmax_ref(q, k, v, *, causal=True, window=0, softcap=0.0):
    """Float32 softmax attention over the dense (Sq, Sk) scores, GQA by
    repeating kv heads; query i and key j sit at positions i and j."""
    B, Sq, H, Dq = q.shape
    Sk = k.shape[1]
    G = H // k.shape[2]
    k, v = (jnp.repeat(x.astype(jnp.float32), G, axis=2) for x in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), k,
                   precision=jax.lax.Precision.HIGHEST) / math.sqrt(Dq)
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    qpos, kpos = jnp.arange(Sq)[:, None], jnp.arange(Sk)[None, :]
    ok = jnp.ones((Sq, Sk), bool)
    if causal:
        ok = ok & (kpos <= qpos)
    if window:
        ok = ok & (kpos > qpos - window)
    w = jax.nn.softmax(jnp.where(ok, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", w, v,
                      precision=jax.lax.Precision.HIGHEST)


def _chunked(q, k, v, *, causal=True, window=0, softcap=0.0, chunk=128):
    B, Sq = q.shape[:2]
    q_pos = jnp.arange(Sq)[None].repeat(B, 0)
    k_pos = jnp.arange(k.shape[1])[None].repeat(B, 0)
    return layers.chunked_attention(q, k, v, q_pos, k_pos, causal=causal,
                                    window=window, softcap=softcap,
                                    q_chunk=chunk, k_chunk=chunk)


def _rel_err(a, b):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))


def _qkv(B, Sq, Sk, H, KH, D, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    return (jax.random.normal(ks[0], (B, Sq, H, D)).astype(dtype),
            jax.random.normal(ks[1], (B, Sk, KH, D)).astype(dtype),
            jax.random.normal(ks[2], (B, Sk, KH, D)).astype(dtype))


_MASKS = dict(causal=(True, 0, 0.0), window=(True, 24, 0.0),
              softcap=(True, 0, 50.0), bidirectional=(False, 0, 0.0))


@pytest.mark.parametrize("B,Sq,Sk,H,KH,D", [
    (2, 64, 64, 4, 2, 32),
    (1, 128, 128, 2, 2, 64),
    (2, 33, 65, 3, 1, 16),
    (1, 256, 256, 8, 4, 8),
])
@pytest.mark.parametrize("mask", sorted(_MASKS))
def test_chunked_matches_softmax(B, Sq, Sk, H, KH, D, mask):
    """Float32, 32-row chunks: padded tails (33 and 65 rows), GQA and
    MQA, Sq != Sk, and each mask."""
    causal, window, softcap = _MASKS[mask]
    q, k, v = _qkv(B, Sq, Sk, H, KH, D)
    kw = dict(causal=causal, window=window, softcap=softcap)
    np.testing.assert_allclose(np.asarray(_chunked(q, k, v, chunk=32, **kw)),
                               np.asarray(_softmax_ref(q, k, v, **kw)),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_chunked_matches_softmax_in_dtype(dtype):
    """Output in the operands' dtype, accumulated in float32."""
    q, k, v = _qkv(1, 64, 64, 2, 2, 32, dtype)
    out = _chunked(q, k, v, chunk=32)
    assert out.dtype == dtype
    tol = BF16_TOL if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(_softmax_ref(q, k, v), np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("causal,window,softcap", [
    (True, 0, 0.0), (True, 16, 30.0),
], ids=["causal", "window_softcap"])
def test_chunked_grads_match_softmax(causal, window, softcap):
    """q/k/v gradients through the chunk loop's online softmax, with the
    window and softcap carried into the backward."""
    q, k, v = _qkv(1, 48, 48, 2, 2, 16)
    kw = dict(causal=causal, window=window, softcap=softcap)
    gc = jax.grad(lambda *a: jnp.sum(_chunked(*a, chunk=16, **kw) ** 2),
                  (0, 1, 2))(q, k, v)
    gr = jax.grad(lambda *a: jnp.sum(_softmax_ref(*a, **kw) ** 2),
                  (0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gc, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4, err_msg=name)


@pytest.mark.parametrize("block", [128, 256])
@pytest.mark.parametrize("heads", [(4, 4, 128, 128), (4, 2, 128, 128),
                                   (4, 4, 192, 128)],
                         ids=["mha", "gqa", "mla"])
def test_splash_matches_chunked_and_softmax(heads, block):
    """Outputs and q/k/v gradients of the splash core (interpret mode,
    bf16 operands) against the chunked core and a float32 softmax, at
    MHA, GQA and MLA (qk 192 / v 128) head shapes, B 2."""
    H, KH, Dq, Dv = heads
    B, S = 2, 512
    ks = jax.random.split(jax.random.PRNGKey(block + Dq + KH), 4)
    q = jax.random.normal(ks[0], (B, S, H, Dq), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, S, KH, Dq), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, S, KH, Dv), jnp.bfloat16)
    dout = jax.random.normal(ks[3], (B, S, H, Dv), jnp.float32)

    def splash(q, k, v):
        return layers.splash_attention(q, k, v, block=block, interpret=True)

    def vjp(core):
        out, pull = jax.vjp(core, q, k, v)
        return out, pull(dout.astype(out.dtype))

    out, grads = vjp(splash)
    assert out.shape == (B, S, H, Dv) and out.dtype == jnp.bfloat16
    for core in (_chunked, _softmax_ref):
        ref_out, ref_grads = vjp(core)
        assert _rel_err(out, ref_out) < BF16_TOL, core.__name__
        for name, g, r in zip("qkv", grads, ref_grads):
            assert g.shape == r.shape
            assert _rel_err(g, r) < BF16_TOL, (core.__name__, name)


def _shapes(B, S, H, Dq, Dv):
    return (jax.ShapeDtypeStruct((B, S, H, Dq), jnp.bfloat16),
            jax.ShapeDtypeStruct((B, S, H, Dv), jnp.bfloat16))


_QWEN = get_config("qwen2-moe-a2.7b")
_MLA = get_config("deepseek-v3-671b")


@pytest.mark.parametrize("cfg,call,why", [
    (_QWEN, dict(causal=True, window=0, softcap=0.0), "backend cpu"),
    (_QWEN, dict(causal=True, window=4096, softcap=0.0), "window 4096"),
    (_QWEN, dict(causal=True, window=0, softcap=50.0), "softcap 50.0"),
    (_QWEN, dict(causal=False, window=0, softcap=0.0), "not causal"),
], ids=["cpu", "window", "softcap", "bidirectional"])
def test_core_path_keeps_chunked(monkeypatch, cfg, call, why):
    if why != "backend cpu":
        monkeypatch.setattr(layers, "_on_one_tpu", lambda: True)
    q, v = _shapes(2, 2048, 16, 128, 128)
    assert layers.attention_core_path(cfg, q, v, **call) == "chunked"


@pytest.mark.parametrize("S", [1, 100, 2000])
def test_core_path_keeps_chunked_off_the_block_grid(monkeypatch, S):
    monkeypatch.setattr(layers, "_on_one_tpu", lambda: True)
    q, v = _shapes(2, S, 16, 128, 128)
    assert layers.attention_core_path(_QWEN, q, v, causal=True, window=0,
                                      softcap=0.0) == "chunked"


def test_core_path_needs_one_device(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda: 4)
    q, v = _shapes(2, 2048, 16, 128, 128)
    assert layers.attention_core_path(_QWEN, q, v, causal=True, window=0,
                                      softcap=0.0) == "chunked"


@pytest.mark.parametrize("cfg,shape", [
    (_QWEN, (2, 2048, 16, 128, 128)),
    (_MLA, (1, 8192, 16, 192, 128)),
    (_QWEN.replace(use_pallas=True), (2, 2048, 16, 128, 128)),
], ids=["qwen2moe.tune", "moonlight.tune", "use_pallas"])
def test_core_path_takes_splash_at_the_cells_shapes(monkeypatch, cfg,
                                                    shape):
    """Splash at both cells' shapes, whatever ``use_pallas`` says."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    q, v = _shapes(*shape)
    assert layers.attention_core_path(cfg, q, v, causal=True, window=0,
                                      softcap=0.0) == "splash"


def test_core_path_logs_once_per_reason(monkeypatch, caplog):
    monkeypatch.setattr(layers, "_CORE_LOGGED", set())
    monkeypatch.setattr(layers, "_on_one_tpu", lambda: True)
    caplog.set_level(logging.INFO, logger=layers.__name__)
    mla, short = _shapes(1, 8192, 16, 192, 128), _shapes(2, 100, 16, 128, 128)
    for q, v in (mla, mla, short, short):
        layers.attention_core_path(_MLA, q, v, causal=True, window=0,
                                   softcap=0.0)
    assert [r.getMessage() for r in caplog.records] == [
        "attention core: splash (causal, 1 x 8192 x 16, qk 192 / v 128, "
        "blocks 1024)",
        "attention core: chunked (S 100 not a multiple of 128)",
    ]


@pytest.mark.parametrize("S,width,itemsize,block", [
    (8192, 192, 2, 1024), (2048, 128, 2, 1024), (8192, 192, 4, 512),
    (4096, 256, 4, 512), (1536, 128, 2, 512), (640, 64, 2, 128),
])
def test_splash_block_divides_s_within_the_tile(S, width, itemsize, block):
    assert layers.splash_block(S, width, itemsize) == block


def _batch(cfg, S, B=2):
    toks = jax.random.randint(jax.random.PRNGKey(0), (B, S), 0,
                              cfg.vocab_size)
    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, 1)}
    front = jnp.ones((B, cfg.frontend_tokens, cfg.d_model), jnp.float32)
    if cfg.arch_type == "vlm":
        batch["patches"] = front
    if cfg.arch_type == "encdec":
        batch["frames"] = front
    return batch


@pytest.mark.parametrize("arch", ["tinyllama-1.1b", "qwen2-moe-a2.7b",
                                  "deepseek-v3-671b", "paligemma-3b",
                                  "whisper-small", "zamba2-7b"])
def test_models_agree_under_the_splash_core(monkeypatch, arch):
    """Every full-sequence caller passes positions 0..S-1, so the splash
    core (masking by index) gives the chunked core's loss and gradients:
    dense GQA, MoE, MLA with its MTP head, the VLM's patches + text, the
    decoder of the encoder-decoder, and the hybrid's shared block."""
    cfg = get_config(arch, variant="reduced")
    params = M.init_params(jax.random.PRNGKey(1), cfg)
    S = 128 - cfg.frontend_tokens if cfg.arch_type == "vlm" else 128
    batch = _batch(cfg, S)

    def step(params):   # traced anew on each call, under the patches in force
        return jax.jit(jax.value_and_grad(
            lambda p: M.loss_fn(p, cfg, batch)[0]))(params)

    loss, grads = step(params)

    taken = []
    splash = layers.splash_attention
    monkeypatch.setattr(layers, "_on_one_tpu", lambda: True)
    monkeypatch.setattr(layers, "splash_attention",
                        lambda *a, **kw: taken.append(1) or splash(*a, **kw))
    loss_s, grads_s = step(params)
    assert taken, "the splash core was not taken"
    assert abs(float(loss_s) - float(loss)) <= 1e-4 * abs(float(loss))
    for g, r in zip(jax.tree.leaves(grads_s), jax.tree.leaves(grads)):
        assert float(jnp.max(jnp.abs(g - r))) <= (
            1e-3 * float(jnp.max(jnp.abs(r))) + 1e-7)
