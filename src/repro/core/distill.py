"""Cross-architecture knowledge distillation (paper §IV.C).

``L_KD = L_CE + α·L_FM + β·L_KL`` (Eq. 11):

* L_CE — student's own autoregressive loss on (public) server data;
* L_FM — VAA feature matching across J representation stages (Eq. 9);
* L_KL — KL(teacher ‖ student) over next-token distributions (Eq. 10),
  computed *sequence-chunked* so (B, S, V) teacher+student logits are
  never materialised at once (on TPU the fused ``kd_loss`` Pallas kernel
  does the same in VMEM tiles — the KD-server hot spot for 100k+ vocabs).

The teacher runs once per batch (no gradients); its stage features and
final hidden states are cached and reused by the student update.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import model as M
from repro.models.config import ModelConfig
from repro.core import vaa as vaa_mod
from repro.optim import scan_epoch
from repro.utils import scopes


# ---------------------------------------------------------------------------
# stage selection
# ---------------------------------------------------------------------------

def select_stages(stages, n_stages: int) -> List[jax.Array]:
    """(nG, B, S, D) scan outputs -> J evenly spaced stage tensors."""
    nG = stages.shape[0]
    idx = np.unique(np.round(np.linspace(1, nG, n_stages)).astype(int) - 1)
    while len(idx) < n_stages:  # tiny models: repeat last stage
        idx = np.append(idx, idx[-1])
    return [stages[i] for i in idx]


def teacher_forward(t_params, t_cfg: ModelConfig, batch, *, n_stages: int,
                    mesh=None):
    """Frozen teacher pass.  Returns dict with stage features + final h."""
    h, _, _, stages = M.backbone(t_params, t_cfg, batch, mesh=mesh,
                                 collect_stages=True)
    return {
        "h": jax.lax.stop_gradient(h),
        "stages": [jax.lax.stop_gradient(s)
                   for s in select_stages(stages, n_stages)],
    }


# ---------------------------------------------------------------------------
# chunked CE + KL
# ---------------------------------------------------------------------------

def _head_w(params, cfg: ModelConfig):
    return params["embed"].T if cfg.tie_embeddings else params["lm_head"]


@scopes.scoped(scopes.KD_LOSS)
def chunked_ce_kl(s_params, s_cfg: ModelConfig, h_s, t_params, t_cfg,
                  h_t, labels, mask, *, temperature: float = 1.0,
                  use_pallas: bool = False):
    """Scan over sequence chunks; returns (ce_sum, kl_sum, tok, correct)."""
    B, S, _ = h_s.shape
    C = min(s_cfg.loss_chunk, S)
    pad = (-S) % C
    if pad:
        h_s = jnp.pad(h_s, ((0, 0), (0, pad), (0, 0)))
        h_t = jnp.pad(h_t, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    n = h_s.shape[1] // C
    hs = h_s.reshape(B, n, C, -1).transpose(1, 0, 2, 3)
    ht = h_t.reshape(B, n, C, -1).transpose(1, 0, 2, 3)
    lc = labels.reshape(B, n, C).transpose(1, 0, 2)
    mc = mask.reshape(B, n, C).transpose(1, 0, 2)
    tau = temperature

    def body(carry, inp):
        ce_s, kl_s, tok_s, cor_s = carry
        hh_s, hh_t, ll, mm = inp
        if use_pallas:
            from repro.kernels.kd_loss import ops as kd_ops
            ce, kl, correct = kd_ops.ce_kl_from_hidden(
                hh_s, _head_w(s_params, s_cfg), hh_t, _head_w(t_params, t_cfg),
                ll, tau=tau,
                softcap_s=s_cfg.final_logit_softcap,
                softcap_t=t_cfg.final_logit_softcap)
        else:
            logit_s = M._head(s_params, s_cfg, hh_s)
            logit_t = jax.lax.stop_gradient(M._head(t_params, t_cfg, hh_t))
            lse_s = jax.nn.logsumexp(logit_s, axis=-1)
            gold = jnp.take_along_axis(logit_s, ll[..., None], -1)[..., 0]
            ce = lse_s - gold
            logp_s = jax.nn.log_softmax(logit_s / tau, axis=-1)
            logp_t = jax.nn.log_softmax(logit_t / tau, axis=-1)
            p_t = jnp.exp(logp_t)
            kl = jnp.sum(p_t * (logp_t - logp_s), axis=-1) * (tau ** 2)
            correct = (jnp.argmax(logit_s, -1) == ll).astype(jnp.float32)
        mmf = mm.astype(jnp.float32)
        return (ce_s + jnp.sum(ce * mmf), kl_s + jnp.sum(kl * mmf),
                tok_s + jnp.sum(mmf), cor_s + jnp.sum(correct * mmf)), 0

    if s_cfg.remat:
        body = jax.checkpoint(body)
    (ce, kl, tok, cor), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32),) * 4, (hs, ht, lc, mc))
    return ce, kl, tok, cor


# ---------------------------------------------------------------------------
# full distillation objective
# ---------------------------------------------------------------------------

def distill_loss(trainable, s_cfg: ModelConfig, t_params, t_cfg: ModelConfig,
                 batch, teacher_out, *, alpha: float = 1.0, beta: float = 1.0,
                 temperature: float = 2.0, n_stages: int = 4,
                 vaa_heads: int = 4, p_q: int = 64, mesh=None):
    """trainable = {"student": student_params, "vaa": vaa_params}.

    Eq. 11: L_KD = L_CE + α L_FM + β L_KL.
    """
    s_params, vaa_params = trainable["student"], trainable["vaa"]
    h_s, aux, _, stages = M.backbone(s_params, s_cfg, batch, mesh=mesh,
                                     collect_stages=True)
    labels = batch["labels"]
    mask = batch.get("mask", jnp.ones_like(labels, jnp.float32))
    ce, kl, tok, cor = chunked_ce_kl(
        s_params, s_cfg, h_s, t_params, t_cfg, teacher_out["h"], labels, mask,
        temperature=temperature, use_pallas=s_cfg.use_pallas)
    ce = ce / jnp.maximum(tok, 1.0)
    kl = kl / jnp.maximum(tok, 1.0)
    s_stages = select_stages(stages, n_stages)
    fm = vaa_mod.feature_matching_loss(
        vaa_params, s_stages, teacher_out["stages"],
        n_heads=vaa_heads, p_q=p_q)
    total = ce + alpha * fm + beta * kl + aux
    metrics = {"ce": ce, "kl": kl, "fm": fm, "aux": aux,
               "accuracy": cor / jnp.maximum(tok, 1.0)}
    return total, metrics


def make_distill_step(s_cfg: ModelConfig, t_cfg: ModelConfig, *, alpha, beta,
                      temperature, n_stages, vaa_heads, p_q, optimizer_update,
                      mesh=None):
    """Builds a jit-able (trainable, opt_state, t_params, batch, lr) step."""

    def step(trainable, opt_state, t_params, batch, lr):
        teacher_out = teacher_forward(t_params, t_cfg, batch,
                                      n_stages=n_stages, mesh=mesh)
        (loss, metrics), grads = jax.value_and_grad(
            distill_loss, has_aux=True)(
                trainable, s_cfg, t_params, t_cfg, batch, teacher_out,
                alpha=alpha, beta=beta, temperature=temperature,
                n_stages=n_stages, vaa_heads=vaa_heads, p_q=p_q, mesh=mesh)
        trainable, opt_state, stats = optimizer_update(
            grads, opt_state, trainable, lr=lr)
        metrics.update(stats)
        return trainable, opt_state, loss, metrics

    return step


def make_distill_epoch(s_cfg: ModelConfig, t_cfg: ModelConfig, *, steps,
                       schedule, alpha, beta, temperature, n_stages,
                       vaa_heads, p_q, optimizer_update, mesh=None):
    """Scan-compiled multi-step distillation (see docs/loops.md).

    Builds a jit-able ``(trainable, opt_state, t_params, batches) ->
    (trainable, opt_state, losses)`` over pre-generated stacked batches
    ``{tokens/labels: (steps, B, S)}``.  The lr ``schedule`` is evaluated
    inside the scan from the step counter, so one compiled program covers
    the whole Phase II epoch with a single host sync at the end.
    """
    step_fn = make_distill_step(
        s_cfg, t_cfg, alpha=alpha, beta=beta, temperature=temperature,
        n_stages=n_stages, vaa_heads=vaa_heads, p_q=p_q,
        optimizer_update=optimizer_update, mesh=mesh)

    def epoch(trainable, opt_state, t_params, batches):
        def carry_step(carry, b, lr):
            trainable, opt_state, loss, _ = step_fn(*carry, t_params, b, lr)
            return (trainable, opt_state), loss

        (trainable, opt_state), losses = scan_epoch(
            carry_step, schedule, steps)((trainable, opt_state), batches)
        return trainable, opt_state, losses

    return epoch
