"""View-Aligned Attention (VAA) — the paper's core module (§IV.C, Fig. 5).

The student (MoE base model) and teacher (proxy of on-device LLMs) have
different architectures and *predictive perspectives*.  VAA lets the
student blend its own multi-stage features through self-attention into a
perspective comparable with the teacher's, after which plain feature
matching (MSE) works.

Three steps (paper numbering):
 1. patchify each student stage j into P_q/J patches and project to a
    common dim d via C_j.  TPU adaptation: the paper's "convolutional
    layers" come from vision KD; on token sequences a non-overlapping
    strided conv == mean-pool over S/P buckets followed by a dense
    projection — a reshaped matmul, MXU-friendly, no halo exchange
    (see DESIGN.md §5).
 2. multi-head self-attention over the concatenated (B, P_q, d) features
    (Eq. 8).
 3. split back into J stages and project each to the teacher's stage
    width; feature-matching loss against the (pooled) teacher stages
    (Eq. 9).
"""
from __future__ import annotations

import math
from typing import List, Sequence

import jax
import jax.numpy as jnp

from repro.models import layers
from repro.utils import scopes


def patchify(x, n_patches: int):
    """(B, S, D) -> (B, n_patches, D) by mean-pooling S into buckets.

    Always returns exactly ``n_patches`` patches: short sequences
    (S < n_patches) are edge-padded up to n_patches first, so downstream
    per-stage slices of the concatenated (B, P_q, d) query block stay
    aligned (vaa_apply step 3) and L_FM shapes always match.
    """
    B, S, D = x.shape
    P = n_patches
    pad = (-S) % P
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)), mode="edge")
    return x.reshape(B, P, -1, D).mean(axis=2)


def init_vaa(key, *, n_stages: int, d_student: int, d_teacher: int,
             d: int = 256, n_heads: int = 4, p_q: int = 64,
             dtype=jnp.float32):
    """Parameters of the VAA module.  p_q = total queries over all stages."""
    assert p_q % n_stages == 0, "P_q must divide into J stages"
    ks = jax.random.split(key, 6)
    return {
        "stage_proj": layers.dense_init(ks[0], (n_stages, d_student, d), 1, dtype),
        "wq": layers.dense_init(ks[1], (d, d), 0, dtype),
        "wk": layers.dense_init(ks[2], (d, d), 0, dtype),
        "wv": layers.dense_init(ks[3], (d, d), 0, dtype),
        "wo": layers.dense_init(ks[4], (d, d), 0, dtype),
        "out_proj": layers.dense_init(ks[5], (n_stages, d, d_teacher), 1, dtype),
    }


@scopes.scoped(scopes.VAA)
def vaa_apply(p, student_stages: Sequence[jax.Array], *, n_heads: int,
              p_q: int) -> List[jax.Array]:
    """student_stages: J tensors (B, S, d_S) -> J tensors (B, P_q/J, d_T)."""
    J = len(student_stages)
    P = p_q // J
    d = p["wq"].shape[0]

    # step 1: patchify + project each stage (Eq. 7)
    feats = []
    for j, f in enumerate(student_stages):
        patches = patchify(f.astype(jnp.float32), P)       # (B, P, d_S)
        feats.append(patches @ p["stage_proj"][j].astype(jnp.float32))
    fs = jnp.concatenate(feats, axis=1)                     # (B, P_q, d)

    # step 2: multi-head self-attention (Eq. 8)
    B = fs.shape[0]
    hd = d // n_heads
    q = (fs @ p["wq"]).reshape(B, -1, n_heads, hd)
    k = (fs @ p["wk"]).reshape(B, -1, n_heads, hd)
    v = (fs @ p["wv"]).reshape(B, -1, n_heads, hd)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", w, v).reshape(B, -1, d)
    fs2 = o @ p["wo"]

    # step 3: split stages + project to teacher widths
    out = []
    for j in range(J):
        blk = fs2[:, j * P:(j + 1) * P]
        out.append(blk @ p["out_proj"][j].astype(jnp.float32))
    return out


@scopes.scoped(scopes.VAA)
def feature_matching_loss(p, student_stages, teacher_stages, *, n_heads: int,
                          p_q: int):
    """L_FM (Eq. 9): MSE between VAA-blended student and pooled teacher."""
    J = len(student_stages)
    P = p_q // J
    blended = vaa_apply(p, student_stages, n_heads=n_heads, p_q=p_q)
    loss = jnp.zeros((), jnp.float32)
    for j in range(J):
        t = patchify(teacher_stages[j].astype(jnp.float32), P)
        t = t / (jnp.linalg.norm(t, axis=-1, keepdims=True) + 1e-6)
        s = blended[j]
        s = s / (jnp.linalg.norm(s, axis=-1, keepdims=True) + 1e-6)
        loss = loss + jnp.mean(jnp.square(s - t))
    return loss / J
