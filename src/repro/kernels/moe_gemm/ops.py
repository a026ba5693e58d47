"""Public wrappers for the grouped expert FFN kernel — trainable.

``grouped_ffn`` carries a ``jax.custom_vjp`` (the pattern proven in
``kernels/kd_loss/ops.py``): the forward is the fused Pallas kernel, the
backward is expressed as grouped GEMMs (the ``grouped_matmul`` kernel,
same contraction structure as the forward) through the gated-activation
chain:

    g = x @ wg          u = x @ wu          h = act(g) * u
    dh = dy @ woᵀ       (dg, du) = vjp of act(g)*u at dh
    dx  = dg @ wgᵀ + du @ wuᵀ
    dwg = xᵀ @ dg       dwu = xᵀ @ du       dwo = hᵀ @ dy

g/u/h are recomputed in the backward (activation recomputation), so the
forward saves only its inputs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.moe_gemm.kernel import grouped_ffn_ecd, grouped_matmul


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


def _gated_act(act: str, g, u):
    a = jax.nn.gelu(g, approximate=True) if act == "gelu" else jax.nn.silu(g)
    return a * u


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _grouped_ffn(x, wg, wu, wo, act, blocks, interpret):
    return grouped_ffn_ecd(x, wg, wu, wo, act=act, block_c=blocks[0],
                           block_f=blocks[1], interpret=interpret)


def _grouped_ffn_fwd(x, wg, wu, wo, act, blocks, interpret):
    out = _grouped_ffn(x, wg, wu, wo, act, blocks, interpret)
    return out, (x, wg, wu, wo)


def _grouped_ffn_bwd(act, blocks, interpret, res, dy):
    # the grouped-matmul kernel widens each tile to f32 and accumulates
    # in f32, so operands keep their storage dtype here (no whole-tensor
    # f32 copies of the expert weights) and the weight gradients are
    # written straight in the weights' dtype
    x, wg, wu, wo = res
    gmm = functools.partial(grouped_matmul, interpret=interpret,
                            out_dtype=jnp.float32)
    tr = lambda a: jnp.swapaxes(a, -1, -2)
    g = gmm(x, wg)                               # (E, C, F)
    u = gmm(x, wu)
    h, h_vjp = jax.vjp(functools.partial(_gated_act, act), g, u)
    dh = gmm(dy, tr(wo))                         # (E, C, F)
    dg, du = h_vjp(dh)
    dx = gmm(dg, tr(wg)) + gmm(du, tr(wu))
    dwg = gmm(tr(x), dg, out_dtype=wg.dtype)     # (E, D, F)
    dwu = gmm(tr(x), du, out_dtype=wu.dtype)
    dwo = gmm(tr(h), dy, out_dtype=wo.dtype)     # (E, F, D)
    return dx.astype(x.dtype), dwg, dwu, dwo


_grouped_ffn.defvjp(_grouped_ffn_fwd, _grouped_ffn_bwd)


@functools.partial(jax.jit, static_argnames=("act", "block_c", "block_f",
                                             "interpret"))
def grouped_ffn(x, wg, wu, wo, *, act: str = "silu", block_c: int = 128,
                block_f: int = 128, interpret: bool | None = None):
    """Fixed-capacity grouped FFN — drop-in for the a2a expert compute."""
    if interpret is None:
        interpret = _on_cpu()
    return _grouped_ffn(x, wg, wu, wo, act, (block_c, block_f), interpret)
