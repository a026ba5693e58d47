"""Compile the main-path Pallas kernels for a described TPU v5e.

No chip is needed: the TPU compiler is installed, and it compiles for a
``v5e:2x2`` topology that is described, not attached.  Interpret mode
cannot see what Mosaic refuses (block shapes off the (8, 128) tiling,
dynamic slices of packed rows, scoped-VMEM overflow), so each kernel of
the training and serving path is compiled here at the widths of
Qwen1.5-MoE-A2.7B (d_model 2048, 16 x 128 heads, 60 experts,
moe_d_ff 1408, vocab 151936) with ``interpret=False``, and the compiled
program must hold the kernel as a ``tpu_custom_call``.

The topology is described inside a fixture: only one process at a time
may load the TPU library, so nothing here touches it while the module
is imported.
"""
import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.kd_loss.ops import ce_from_hidden, ce_kl_from_hidden
from repro.kernels.moe_dispatch.kernel import gather_scatter_add_rows
from repro.kernels.moe_gemm.ops import grouped_ffn
from repro.kernels.paged_attn.ops import paged_decode_attention
from repro.models import layers, moe
from repro.models.config import ModelConfig

D, H, DH, E, F, V = 2048, 16, 128, 60, 1408, 151936
TOP_K = 4
D_TEACHER = 1024          # gpt2-medium, the widest device model
HBM_BYTES = 16 * 1024 ** 3
BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler / plugin in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _lower(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile()


def _compile(fn, sharding, *shapes):
    compiled = _lower(fn, sharding, *shapes)
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "kernel missing from the TPU program"
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < HBM_BYTES, f"{used} bytes do not fit one v5e chip"
    return compiled


def test_flash_attention_fwd(one_chip):
    q = ((2, 512, H, DH), BF16)
    fn = functools.partial(flash_attention, causal=True, interpret=False)
    _compile(fn, one_chip, q, q, q)


@pytest.mark.parametrize("B,S,Dq,Dv", [(2, 2048, DH, DH), (1, 8192, 192, 128)],
                         ids=["qwen2moe.tune", "moonlight.tune"])
def test_splash_attention_fwd_and_grad(one_chip, B, S, Dq, Dv):
    """The training attention core at both benchmark cells' shapes (the
    MLA one with qk 192 / v 128): the forward kernel, then the backward's
    dk/dv and dq kernels."""
    fwd = functools.partial(layers.splash_attention, interpret=False)
    q, v = ((B, S, H, Dq), BF16), ((B, S, H, Dv), BF16)
    _compile(fwd, one_chip, q, q, v)

    def loss(q, k, v):
        return jnp.sum(fwd(q, k, v).astype(F32))

    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), one_chip,
                    q, q, v).as_text()
    assert text.count("tpu_custom_call") >= 3


def test_grouped_ffn_fwd_and_grad(one_chip):
    x = ((E, 64, D), BF16)
    w_in, w_out = ((E, D, F), BF16), ((E, F, D), BF16)
    fwd = functools.partial(grouped_ffn, interpret=False)
    _compile(fwd, one_chip, x, w_in, w_in, w_out)

    def loss(x, wg, wu, wo):
        return jnp.sum(fwd(x, wg, wu, wo).astype(F32))

    text = _compile(jax.grad(loss, argnums=(0, 1, 2, 3)), one_chip,
                    x, w_in, w_in, w_out).as_text()
    # forward kernel + the grouped-matmul backward kernels
    assert text.count("tpu_custom_call") >= 2


@pytest.mark.parametrize("dtype", [BF16, F32], ids=["bf16", "f32"])
def test_gather_scatter_add_rows(one_chip, dtype):
    n_src, n_out, rows = 256, 512, 1024

    def fn(src, src_rows, dst_rows, scale):
        return gather_scatter_add_rows(src, src_rows, dst_rows, scale, n_out,
                                       interpret=False)

    _compile(fn, one_chip, ((n_src, D), dtype), ((rows,), I32),
             ((rows,), I32), ((rows,), F32))


def test_ce_kl_from_hidden(one_chip):
    T = 512

    def fn(hs, ws, ht, wt, labels):
        return ce_kl_from_hidden(hs, ws, ht, wt, labels, tau=2.0,
                                 interpret=False)

    _compile(fn, one_chip, ((T, D), BF16), ((D, V), BF16),
             ((T, D_TEACHER), BF16), ((D_TEACHER, V), BF16), ((T,), I32))


def test_ce_from_hidden(one_chip):
    T = 512

    def fn(hs, ws, labels):
        return ce_from_hidden(hs, ws, labels, interpret=False)

    _compile(fn, one_chip, ((T, D), BF16), ((D, V), BF16), ((T,), I32))


@pytest.mark.parametrize("C", [1, 4])
@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_paged_decode_attention(one_chip, C, kv):
    B, n_blocks, bl, nbt = 4, 64, 16, 8
    pool_dt = BF16 if kv == "bf16" else jnp.int8
    shapes = [((B, C, H, DH), BF16), ((n_blocks, bl, H, DH), pool_dt),
              ((n_blocks, bl, H, DH), pool_dt), ((B, nbt), I32), ((B,), I32)]
    if kv == "int8":
        shapes += [((n_blocks, bl, H), F32)] * 2

        def fn(q, k, v, bt, pos, ks, vs):
            return paged_decode_attention(q, k, v, bt, pos, interpret=False,
                                          k_scale=ks, v_scale=vs,
                                          out_dtype=BF16)
    else:
        def fn(q, k, v, bt, pos):
            return paged_decode_attention(q, k, v, bt, pos, interpret=False)

    _compile(fn, one_chip, *shapes)


def test_grouped_moe_fwd_and_grad(one_chip):
    """The one-chip MoE path at the cell's size (2 x 2048 tokens, top-4
    of 60): XLA's grouped-matmul custom calls over the T*k routed rows,
    no (E, T, .) all-experts buffer, and less temporary memory than the
    all-experts reference at the same shape."""
    T = 4096
    cfg = ModelConfig(name="qwen1.5-moe", arch_type="moe", n_layers=1,
                      d_model=D, n_heads=H, n_kv_heads=H, head_dim=DH,
                      d_ff=4 * F, vocab_size=V, n_experts=E, top_k=TOP_K,
                      moe_d_ff=F, dtype="bfloat16").validate()
    shapes = [((D, E), F32), ((E, D, F), BF16), ((E, D, F), BF16),
              ((E, F, D), BF16), ((1, T, D), BF16)]

    def fwd(impl):
        def f(router, wg, wu, wo, x):
            p = dict(router=router, wi_gate=wg, wi_up=wu, wo=wo)
            return moe.apply_moe(p, cfg.replace(moe_impl=impl), x)[0]
        return f

    def grad(impl):
        return jax.grad(lambda *a: jnp.sum(fwd(impl)(*a).astype(F32)),
                        argnums=(0, 1, 2, 3, 4))

    all_experts = re.compile(rf"\[{E},{T},\d+\]")
    for program in (fwd, grad):
        grouped = _compile(program("grouped"), one_chip, *shapes)
        text = grouped.as_text()
        assert "ragged-dot" in text
        assert not all_experts.search(text)
        dense = _lower(program("dense"), one_chip, *shapes)
        assert all_experts.search(dense.as_text())
        assert (grouped.memory_analysis().temp_size_in_bytes
                < dense.memory_analysis().temp_size_in_bytes)
