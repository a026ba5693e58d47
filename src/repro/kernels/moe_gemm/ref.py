"""jnp oracle for the grouped expert FFN."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def grouped_ffn_ref(x, wg, wu, wo, *, act: str = "silu"):
    """x: (E, C, D); wg/wu: (E, D, F); wo: (E, F, D) -> (E, C, D)."""
    xf = x.astype(jnp.float32)
    g = jnp.einsum("ecd,edf->ecf", xf, wg.astype(jnp.float32))
    g = jax.nn.gelu(g, approximate=True) if act == "gelu" else jax.nn.silu(g)
    h = g * jnp.einsum("ecd,edf->ecf", xf, wu.astype(jnp.float32))
    y = jnp.einsum("ecf,efd->ecd", h, wo.astype(jnp.float32))
    return y.astype(x.dtype)


def grouped_ffn_bwd_ref(x, wg, wu, wo, dy, *, act: str = "silu"):
    """Explicit-chain reference backward for ``grouped_ffn``.

    Returns (dx, dwg, dwu, dwo) in f32 — the oracle the custom-VJP
    grouped-GEMM backward is tested against (independent of jax.grad).
    """
    xf = x.astype(jnp.float32)
    wgf, wuf, wof = (t.astype(jnp.float32) for t in (wg, wu, wo))
    dyf = dy.astype(jnp.float32)
    g = jnp.einsum("ecd,edf->ecf", xf, wgf)
    u = jnp.einsum("ecd,edf->ecf", xf, wuf)
    if act == "gelu":
        a = jax.nn.gelu(g, approximate=True)
        # d/dg of tanh-approx gelu
        c = jnp.sqrt(2.0 / jnp.pi)
        inner = c * (g + 0.044715 * g**3)
        t = jnp.tanh(inner)
        da = 0.5 * (1.0 + t) + 0.5 * g * (1.0 - t * t) * c * (
            1.0 + 3 * 0.044715 * g * g)
    else:
        s = jax.nn.sigmoid(g)
        a = g * s
        da = s * (1.0 + g * (1.0 - s))
    h = a * u
    dh = jnp.einsum("ecd,efd->ecf", dyf, wof)
    dg = dh * u * da
    du = dh * a
    dx = (jnp.einsum("ecf,edf->ecd", dg, wgf)
          + jnp.einsum("ecf,edf->ecd", du, wuf))
    dwg = jnp.einsum("ecd,ecf->edf", xf, dg)
    dwu = jnp.einsum("ecd,ecf->edf", xf, du)
    dwo = jnp.einsum("ecf,ecd->efd", h, dyf)
    return dx, dwg, dwu, dwo
