"""Shared MoE dispatch/combine: the single definition of routing layout,
capacity accounting and drop semantics for every MoE execution path.

``capacity_positions`` ranks each (token, expert) assignment within its
expert; ``token_dispatch`` / ``token_combine`` move rows between the
flat token array and flat capacity slots.  Both movements are one
``gather_scatter_add`` primitive carrying a ``jax.custom_vjp`` whose
backward is the same primitive with source/destination swapped — so the
Pallas data-movement kernel is trainable end-to-end, mirroring the
custom-VJP pattern of ``kernels/kd_loss/ops.py``.

``use_kernel=False`` selects a pure-XLA ``.at[].add`` implementation
(natively differentiable) for the non-Pallas model configs; both
implementations share the same index/mask computation, so the three
``models/moe.py`` paths agree on which tokens drop.
"""
from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.moe_dispatch.kernel import (VMEM_BUDGET_BYTES, fits_vmem,
                                               gather_scatter_add_rows)


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


_DISPATCH_PATH_LOGGED: set = set()


def dispatch_path(n_src: int, n_out: int, d: int, *, use_kernel: bool,
                  interpret: bool) -> str:
    """Which implementation moves a dispatch/combine's rows: ``"pallas"``
    (the fused row kernel) or ``"xla"`` (the ``.at[].add`` scatter).

    The kernel keeps its source and f32 accumulator whole in VMEM, so
    the choice follows the shape.  It is logged once per distinct
    reason, like ``layers.paged_read_path``, so a run on the chip shows
    which path every MoE call took."""
    if not use_kernel:
        path, why = "xla", "use_pallas=False"
    elif interpret:
        path, why = "pallas", "interpret mode"
    elif fits_vmem(n_src, n_out, d):
        path, why = "pallas", f"{n_src}+{n_out} rows x {d} fit VMEM"
    else:
        path, why = "xla", (f"{n_src}+{n_out} rows x {d} exceed the "
                            f"{VMEM_BUDGET_BYTES} B VMEM budget")
    if (path, why) not in _DISPATCH_PATH_LOGGED:
        _DISPATCH_PATH_LOGGED.add((path, why))
        logging.getLogger(__name__).info("moe_dispatch path: %s (%s)",
                                         path, why)
    return path


def capacity_positions(flat_e, cap: int, valid=None):
    """Rank of each assignment within its expert + keep mask.

    flat_e: (N,) expert ids.  Returns (pos (N,) int32, keep (N,) bool)
    where ``pos`` is the arrival rank among equal expert ids (stable in
    token order — GShard drop semantics) and ``keep = pos < cap``.

    ``valid`` (N,) bool marks assignments that exist at all (serving:
    tokens from live engine slots).  Invalid assignments are ranked in a
    sentinel bucket past every real expert id, so they consume NO
    capacity rank inside any expert — a freed slot's garbage lane can
    never crowd a live token out of an expert — and are always dropped
    (``keep`` is False for them).
    """
    n = flat_e.shape[0]
    key = flat_e
    if valid is not None:
        key = jnp.where(valid, flat_e, jnp.iinfo(flat_e.dtype).max)
    order = jnp.argsort(key, stable=True)
    sorted_e = key[order]
    pos_sorted = jnp.arange(n) - jnp.searchsorted(sorted_e, sorted_e, "left")
    pos = jnp.zeros((n,), jnp.int32).at[order].set(pos_sorted.astype(jnp.int32))
    keep = pos < cap
    if valid is not None:
        keep = keep & valid
    return pos, keep


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _gsa(src, scale, src_rows, dst_rows, n_out, interpret):
    return gather_scatter_add_rows(src, src_rows, dst_rows, scale, n_out,
                                   interpret=interpret)


def _gsa_fwd(src, scale, src_rows, dst_rows, n_out, interpret):
    out = _gsa(src, scale, src_rows, dst_rows, n_out, interpret)
    return out, (src, scale, src_rows, dst_rows)


def _gsa_bwd(n_out, interpret, res, dout):
    src, scale, src_rows, dst_rows = res
    doutf = dout.astype(jnp.float32)
    # transpose of a scatter-add is the same movement, reversed
    dsrc = gather_scatter_add_rows(doutf, dst_rows, src_rows, scale,
                                   src.shape[0], interpret=interpret)
    dscale = jnp.einsum("rd,rd->r", src[src_rows].astype(jnp.float32),
                        doutf[dst_rows])
    zero_i = np.zeros(src_rows.shape, dtype=jax.dtypes.float0)
    return (dsrc.astype(src.dtype), dscale.astype(scale.dtype),
            zero_i, np.zeros(dst_rows.shape, dtype=jax.dtypes.float0))


_gsa.defvjp(_gsa_fwd, _gsa_bwd)


def token_dispatch(xt, flat_tok, slot, keep, n_slots: int, *,
                   use_kernel: bool = True, interpret: bool | None = None):
    """Pack tokens into flat capacity slots: out (n_slots, D) with
    ``out[slot[i]] += xt[flat_tok[i]]`` for kept assignments."""
    if interpret is None:
        interpret = _on_cpu()
    scale = keep.astype(jnp.float32)
    dst = jnp.where(keep, slot, 0).astype(jnp.int32)
    if dispatch_path(xt.shape[0], n_slots, xt.shape[1], use_kernel=use_kernel,
                     interpret=interpret) == "pallas":
        return _gsa(xt, scale, flat_tok.astype(jnp.int32), dst, n_slots,
                    interpret)
    return jnp.zeros((n_slots, xt.shape[1]), xt.dtype).at[dst].add(
        scale[:, None].astype(xt.dtype) * xt[flat_tok])


def token_combine(y2d, flat_tok, slot, keep, weights, n_tokens: int, *,
                  use_kernel: bool = True, interpret: bool | None = None):
    """Unpack expert outputs back to tokens, applying routing weights:
    out (n_tokens, D) with ``out[flat_tok[i]] += w[i] * y2d[slot[i]]``
    for kept assignments (dropped assignments contribute zero)."""
    if interpret is None:
        interpret = _on_cpu()
    scale = jnp.where(keep, weights, 0.0)
    srcr = jnp.where(keep, slot, 0).astype(jnp.int32)
    if dispatch_path(y2d.shape[0], n_tokens, y2d.shape[1],
                     use_kernel=use_kernel, interpret=interpret) == "pallas":
        return _gsa(y2d, scale, srcr, flat_tok.astype(jnp.int32), n_tokens,
                    interpret)
    gathered = jnp.where(keep[:, None], y2d[srcr], 0.0)
    return jnp.zeros((n_tokens, y2d.shape[1]), y2d.dtype).at[flat_tok].add(
        gathered * scale[:, None].astype(y2d.dtype))
