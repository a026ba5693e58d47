"""Fused token permute/unpermute (Pallas TPU) — the MoE dispatch/combine
data movement.

One primitive covers all four movements of a routed MoE layer:

    out[dst_rows[i], :] += scale[i] * src[src_rows[i], :]      i = 0..R-1

* dispatch  = gather tokens, scatter into capacity slots (scale = keep)
* combine   = gather slots, scatter-add into tokens (scale = w * keep)
* their backwards are the same primitive with src/dst swapped.

Row indices and scales ride in SMEM via scalar prefetch; src (widened
to f32) and the f32 accumulator live whole in VMEM.  That bounds the
kernel to movements whose src + out fit the VMEM budget — ``ops.token_dispatch`` /
``token_combine`` check ``fits_vmem`` and fall back to the XLA
scatter-add implementation for larger buffers (e.g. the a2a send buffer
at production ep_size; a row-tiled multi-pass variant is a listed
follow-up).  The row loop is a sequential ``fori_loop`` — the scatter
targets are data-dependent, so correctness needs in-order
read-modify-write, and the kernel is DMA-bound regardless.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# v5e-class VMEM is 16 MB; leave headroom for indices + double buffering.
VMEM_BUDGET_BYTES = 8 * 1024 * 1024


def fits_vmem(n_src: int, n_out: int, d: int) -> bool:
    """Whether src + f32 accumulator fit the kernel's whole-in-VMEM design."""
    return 4 * (n_src + n_out) * d <= VMEM_BUDGET_BYTES


def _gsa_kernel(src_rows_ref, dst_rows_ref, scale_ref, src_ref, out_ref):
    out_ref[...] = jnp.zeros_like(out_ref)

    def body(r, _):
        s = src_rows_ref[r]
        d = dst_rows_ref[r]
        c = scale_ref[r]
        out_ref[pl.ds(d, 1), :] += c * src_ref[pl.ds(s, 1), :]
        return 0

    jax.lax.fori_loop(0, src_rows_ref.shape[0], body, 0)


def gather_scatter_add_rows(src, src_rows, dst_rows, scale, n_out: int, *,
                            interpret: bool = False):
    """src: (Ns, D); src_rows/dst_rows: (R,) int32; scale: (R,) -> (n_out, D).

    Accumulates in f32, returns ``src.dtype``.  The source is widened to
    f32 before the call: the kernel moves one dynamic row at a time, and
    a single row of a packed 16-bit array is not a whole sublane tile.
    Out-of-capacity rows are expressed as ``scale == 0`` (the row still
    moves, adds nothing), so index arrays never need masking beyond
    clamping into range.
    """
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(1,),
        in_specs=[pl.BlockSpec(src.shape, lambda i, *refs: (0, 0))],
        out_specs=pl.BlockSpec((n_out, src.shape[1]), lambda i, *refs: (0, 0)),
    )
    out = pl.pallas_call(
        _gsa_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_out, src.shape[1]), jnp.float32),
        interpret=interpret,
    )(src_rows.astype(jnp.int32), dst_rows.astype(jnp.int32),
      scale.astype(jnp.float32), src.astype(jnp.float32))
    return out.astype(src.dtype)
