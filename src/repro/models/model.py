"""Model assembly: init / loss / prefill / decode for every arch family.

Layer parameters are **stacked** along a leading "group" axis and the
layer stack executes under ``jax.lax.scan`` — the HLO stays compact no
matter how deep the model is (81-layer Zamba-2 and 61-layer DeepSeek-V3
compile in seconds on the 512-device placeholder mesh).

Layouts:
  dense/moe/vlm : blocks are groups of ``len(cfg.attn_pattern)`` sub-layers
                  (gemma-2 alternates local/global inside one group).
  moe w/ leading dense layers (DeepSeek): two stacks, scanned in sequence.
  ssm           : one stack of Mamba-2 blocks.
  hybrid        : (groups, period) nested stacks of Mamba-2 blocks with one
                  *shared* attention block applied at the top of each group
                  (Zamba-2's parameter-sharing trick) + a tail stack.
  encdec        : encoder stack + decoder stack with cross-attention.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.models.config import ModelConfig
from repro.models import layers, moe, quant, ssm
from repro.utils import scopes


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def _data_axes(mesh):
    if mesh is None:
        return None
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def shard_act(x, mesh, *, batch_dim: int = 0):
    """Constrain an activation's batch dim onto the data axes."""
    if mesh is None or mesh.size == 1:
        return x
    axes = _data_axes(mesh)
    n_data = 1
    for a in axes:
        n_data *= mesh.shape[a]
    if x.shape[batch_dim] % n_data != 0:
        return x  # tiny decode batches (long_500k B=1) stay replicated
    spec = [None] * x.ndim
    spec[batch_dim] = axes if len(axes) > 1 else axes[0]
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*spec)))


def _stacked_init(fn, key, n: int):
    """vmap an init function over a leading group axis."""
    return jax.vmap(fn)(jax.random.split(key, n))


def _window_for(cfg: ModelConfig, kind: str) -> int:
    return cfg.sliding_window if kind == "local" else 0


def _unroll(cfg: ModelConfig, xs) -> int:
    return jax.tree.leaves(xs)[0].shape[0] if cfg.scan_unroll else 1


class Tapped(NamedTuple):
    """A frozen parameter of a loss whose gradient is wanted only for its
    squared norm: the weight ``w``, not differentiated, and a float32
    ``probe`` that is.  A leaf of a stacked tree carries one probe entry
    per layer group, ``(L,)``, and rides ``_scan``'s ``xs`` whole; it is
    tapped once the scan has sliced it to a scalar probe."""
    w: Any
    probe: Any


@jax.custom_vjp
def tap(w, probe):
    """``w`` unchanged.  The backward gives ``w`` no cotangent and
    ``probe`` the float32 squared norm of ``w``'s: reduced where it is
    formed, so no stacked gradient of a frozen leaf is ever written."""
    return w


def _tap_fwd(w, probe):
    return w, None


def _tap_bwd(_, dw):
    return None, jnp.sum(jnp.square(dw.astype(jnp.float32)))


tap.defvjp(_tap_fwd, _tap_bwd)


def untap(tree):
    """Apply ``tap`` to each ``Tapped`` node of ``tree`` whose probe is a
    scalar; the rest, plain arrays included, are returned as they are."""
    def one(x):
        if isinstance(x, Tapped) and jnp.ndim(x.probe) == 0:
            return tap(x.w, x.probe)
        return x
    return jax.tree.map(one, tree, is_leaf=lambda x: isinstance(x, Tapped))


# the decoder's stacked layer trees, which hold the experts; ``_scan``
# runs over their leading layer-group axis
_STACKS = ("blocks", "dense_blocks")


def frozen_probes(params, freeze_mask):
    """Zero float32 probes for ``Tapped``: one entry per layer group for a
    frozen leaf (mask False) of a ``_STACKS`` tree, a scalar for any other
    frozen leaf (tapped whole where the loss starts), None for a
    trainable leaf."""
    def probe(path, p, trainable):
        if trainable:
            return None
        stacked = getattr(path[0], "key", None) in _STACKS
        return jnp.zeros(p.shape[:1] if stacked else (), jnp.float32)
    return jax.tree_util.tree_map_with_path(probe, params, freeze_mask)


@scopes.scoped(scopes.STACK)
def _scan(cfg: ModelConfig, body, init, xs):
    """A scan over stacked layers; the ``Tapped`` leaves of each layer's
    slice of ``xs`` are tapped before ``body`` sees them."""
    return jax.lax.scan(lambda c, x: body(c, untap(x)), init, xs,
                        unroll=_unroll(cfg, xs))


def _maybe_remat(cfg: ModelConfig, fn):
    if not cfg.remat:
        return fn
    if cfg.remat_policy == "dots":
        policy = jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        return jax.checkpoint(fn, policy=policy)
    return jax.checkpoint(fn)


# ---------------------------------------------------------------------------
# transformer block (dense / moe / vlm sub-layer)
# ---------------------------------------------------------------------------

def _init_block(key, cfg: ModelConfig, *, use_moe: bool, dtype):
    ks = jax.random.split(key, 4)
    p: Dict[str, Any] = {"ln1": layers.init_norm(cfg, cfg.d_model, dtype),
                         "ln2": layers.init_norm(cfg, cfg.d_model, dtype)}
    if cfg.attn_type == "mla":
        p["attn"] = layers.init_mla(ks[0], cfg, dtype)
    else:
        p["attn"] = layers.init_attention(ks[0], cfg, dtype)
    if use_moe:
        p["moe"] = moe.init_moe(ks[1], cfg, dtype)
    else:
        p["mlp"] = layers.init_mlp(ks[1], cfg, cfg.d_model, cfg.d_ff, dtype)
    if cfg.post_block_norm:
        p["ln1_post"] = layers.init_norm(cfg, cfg.d_model, dtype)
        p["ln2_post"] = layers.init_norm(cfg, cfg.d_model, dtype)
    return p


def _block_attn(p, cfg: ModelConfig, x, positions, *, kind: str,
                causal: bool = True):
    """A full-sequence sub-layer's attention half: x plus its attention.
    Returns (x, cache_entry)."""
    window = _window_for(cfg, kind)
    h = layers.apply_norm(p["ln1"], x, cfg.norm_eps)
    if cfg.attn_type == "mla":
        attn_out, (ckv, kr) = layers.mla_full(p["attn"], cfg, h, positions)
        kv = {"ckv": ckv, "kr": kr}
    else:
        attn_out, (k, v) = layers.attention_full(p["attn"], cfg, h, positions,
                                                 window=window, causal=causal)
        kv = {"k": k, "v": v}
    if cfg.post_block_norm:
        attn_out = layers.apply_norm(p["ln1_post"], attn_out, cfg.norm_eps)
    return x + attn_out, kv


def _block_ffn(p, cfg: ModelConfig, x, *, mesh):
    """A sub-layer's FFN half: x plus its MLP or MoE.  Returns (x, aux)."""
    h = layers.apply_norm(p["ln2"], x, cfg.norm_eps)
    aux = jnp.zeros((), jnp.float32)
    if "moe" in p:
        ffn_out, aux = moe.apply_moe(p["moe"], cfg, h, mesh)
    else:
        with jax.named_scope(scopes.MLP):
            ffn_out = layers.apply_mlp(p["mlp"], cfg, h)
    if cfg.post_block_norm:
        ffn_out = layers.apply_norm(p["ln2_post"], ffn_out, cfg.norm_eps)
    x = x + ffn_out
    return shard_act(x, mesh), aux


def _block_full(p, cfg: ModelConfig, x, positions, *, kind: str, mesh,
                causal: bool = True):
    """Full-sequence sub-layer.  Returns (x, aux, cache_entry)."""
    x, kv = _block_attn(p, cfg, x, positions, kind=kind, causal=causal)
    x, aux = _block_ffn(p, cfg, x, mesh=mesh)
    return x, aux, kv


def _block_decode(p, cfg: ModelConfig, x, pos, cache, *, kind: str, mesh,
                  block_tables=None, write_tables=None, live=None):
    """Decode / chunked-prefill sub-layer.  x: (B, C, D), pos: (B, C) —
    C=1 is the single-token decode step.  cache: dict of per-layer
    tensors (contiguous (B, S, ...) rows, or block pools when
    ``block_tables`` (B, nbt) is given; ``write_tables`` diverts chunked
    admission writes for already-pooled shared prefix blocks).
    ``live`` (B, C) bool masks dead serving rows (freed slots, bucket
    pads) out of MoE routing weights and expert-capacity accounting."""
    window = _window_for(cfg, kind)
    h = layers.apply_norm(p["ln1"], x, cfg.norm_eps)
    if cfg.attn_type == "mla":
        attn_out, new_cache = layers.mla_decode(p["attn"], cfg, h, pos, cache,
                                                mesh=mesh,
                                                block_table=block_tables,
                                                write_table=write_tables)
    else:
        attn_out, new_cache = layers.attention_decode(
            p["attn"], cfg, h, pos, cache, window=window,
            mesh=mesh, block_table=block_tables, write_table=write_tables)
    if cfg.post_block_norm:
        attn_out = layers.apply_norm(p["ln1_post"], attn_out, cfg.norm_eps)
    x = x + attn_out
    h = layers.apply_norm(p["ln2"], x, cfg.norm_eps)
    if "moe" in p:
        ffn_out, _ = moe.apply_moe(p["moe"], cfg, h, mesh, live=live)
    else:
        with jax.named_scope(scopes.MLP):
            ffn_out = layers.apply_mlp(p["mlp"], cfg, h)
    if cfg.post_block_norm:
        ffn_out = layers.apply_norm(p["ln2_post"], ffn_out, cfg.norm_eps)
    # keep decode activations batch-sharded: without this the
    # replicated_ep MoE path leaves x replicated and every subsequent
    # attention layer runs the FULL batch on EVERY device (§Perf D3)
    x = shard_act(x + ffn_out, mesh)
    return x, new_cache


def _attn_cache_struct(cfg: ModelConfig, B: int, S: int, dtype, policy=None):
    """One attention layer's KV cache entry.

    Under a quantized ``CachePolicy`` each KV leaf is stored at the
    policy's dtype with a float32 ``<leaf>_scale`` sibling of the leaf's
    shape minus its trailing feature axis (one scale per written row /
    kv-head) — see ``repro.models.quant``.
    """
    pol = policy or quant.CachePolicy()
    sd = pol.storage_dtype(dtype)
    if cfg.attn_type == "mla":
        c = {"ckv": jnp.zeros((B, S, cfg.kv_lora_rank), sd),
             "kr": jnp.zeros((B, S, cfg.rope_head_dim), sd)}
    else:
        KH, Dh = cfg.n_kv_heads, cfg.resolved_head_dim
        c = {"k": jnp.zeros((B, S, KH, Dh), sd),
             "v": jnp.zeros((B, S, KH, Dh), sd)}
    if pol.quantized:
        for key in list(c):
            c[quant.scale_name(key)] = jnp.zeros(c[key].shape[:-1],
                                                 jnp.float32)
    return c


# ===========================================================================
# dense / moe / vlm family
# ===========================================================================

def _init_decoder_stacks(key, cfg: ModelConfig, dtype):
    lps = cfg.layers_per_scan
    p = {}
    kd, km = jax.random.split(key)
    n_dense_groups = cfg.first_dense_layers  # leading dense layers (deepseek)
    n_main = cfg.n_layers - n_dense_groups
    assert n_main % lps == 0
    n_groups = n_main // lps

    def group_init(k, use_moe):
        ks = jax.random.split(k, lps)
        return {f"sub{i}": _init_block(ks[i], cfg, use_moe=use_moe, dtype=dtype)
                for i in range(lps)}

    if n_dense_groups:
        p["dense_blocks"] = _stacked_init(
            lambda k: {"sub0": _init_block(k, cfg, use_moe=False, dtype=dtype)},
            kd, n_dense_groups)
    p["blocks"] = _stacked_init(
        functools.partial(group_init, use_moe=cfg.is_moe), km, n_groups)
    return p


def _run_stack(blocks, cfg: ModelConfig, x, positions, *, pattern, mesh,
               causal: bool, collect_cache: bool, collect_stages: bool = False):
    """scan over a stacked group of sub-layers (full-sequence)."""

    def group_fn(x, gp):
        aux = jnp.zeros((), jnp.float32)
        caches = {}
        for i, kind in enumerate(pattern):
            x, a, kv = _block_full(gp[f"sub{i}"], cfg, x, positions,
                                   kind=kind, mesh=mesh, causal=causal)
            aux = aux + a
            if collect_cache:
                caches[f"sub{i}"] = kv
        return x, (aux, caches if collect_cache else 0)

    group_fn = _maybe_remat(cfg, group_fn)

    def body(carry, gp):
        x, aux = carry
        x, (a, caches) = group_fn(x, gp)
        return (x, aux + a), (caches, x if collect_stages else 0)

    (x, aux), (caches, stages) = _scan(
        cfg, body, (x, jnp.zeros((), jnp.float32)), blocks)
    return x, aux, caches, stages


def _decode_stack(blocks, cfg: ModelConfig, x, pos, cache, *, pattern, mesh,
                  block_tables=None, write_tables=None, live=None):
    def body(x, inp):
        gp, gc = inp
        new_c = {}
        for i in range(len(pattern)):
            x, nc = _block_decode(gp[f"sub{i}"], cfg, x, pos, gc[f"sub{i}"],
                                  kind=pattern[i], mesh=mesh,
                                  block_tables=block_tables,
                                  write_tables=write_tables, live=live)
            new_c[f"sub{i}"] = nc
        return x, new_c

    x, new_cache = _scan(cfg, body, x, (blocks, cache))
    return x, new_cache


# ===========================================================================
# public API
# ===========================================================================

def init_params(key, cfg: ModelConfig):
    cfg.validate()
    dtype = _dtype(cfg)
    keys = jax.random.split(key, 8)
    p: Dict[str, Any] = {
        "embed": layers.embed_init(keys[0], (cfg.vocab_size, cfg.d_model), dtype),
        "final_norm": layers.init_norm(cfg, cfg.d_model, dtype),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = layers.dense_init(
            keys[1], (cfg.d_model, cfg.vocab_size), 0, dtype)

    at = cfg.arch_type
    if at in ("dense", "moe", "vlm"):
        p.update(_init_decoder_stacks(keys[2], cfg, dtype))
        if cfg.n_mtp:
            p["mtp"] = {
                "proj": layers.dense_init(keys[3], (2 * cfg.d_model, cfg.d_model),
                                          0, dtype),
                "block": _init_block(keys[4], cfg, use_moe=False, dtype=dtype),
                "norm": layers.init_norm(cfg, cfg.d_model, dtype),
            }
    elif at == "ssm":
        p["blocks"] = _stacked_init(
            lambda k: {"ln": layers.init_norm(cfg, cfg.d_model, dtype),
                       "mixer": ssm.init_ssm(k, cfg, dtype)},
            keys[2], cfg.n_layers)
    elif at == "hybrid":
        period = cfg.shared_attn_every
        n_groups, tail = divmod(cfg.n_layers, period)

        def mamba_block(k):
            return {"ln": layers.init_norm(cfg, cfg.d_model, dtype),
                    "mixer": ssm.init_ssm(k, cfg, dtype)}

        p["mamba_groups"] = jax.vmap(lambda k: _stacked_init(mamba_block, k, period))(
            jax.random.split(keys[2], n_groups))
        if tail:
            p["mamba_tail"] = _stacked_init(mamba_block, keys[3], tail)
        # ONE shared attention block reused at the top of every group
        p["shared_attn"] = _init_block(keys[4], cfg, use_moe=False, dtype=dtype)
    elif at == "encdec":
        def enc_block(k):
            return _init_block(k, cfg, use_moe=False, dtype=dtype)

        def dec_block(k):
            ks = jax.random.split(k, 2)
            b = _init_block(ks[0], cfg, use_moe=False, dtype=dtype)
            b["ln_x"] = layers.init_norm(cfg, cfg.d_model, dtype)
            b["xattn"] = layers.init_attention(ks[1], cfg, dtype)
            return b

        p["enc_blocks"] = _stacked_init(enc_block, keys[2], cfg.n_enc_layers)
        p["enc_norm"] = layers.init_norm(cfg, cfg.d_model, dtype)
        p["dec_blocks"] = _stacked_init(dec_block, keys[3], cfg.n_layers)
    else:
        raise ValueError(f"unknown arch_type {at}")
    return p


@scopes.scoped(scopes.EMBED)
def _embed(params, cfg: ModelConfig, tokens):
    x = params["embed"][tokens]
    if cfg.embed_scale:
        x = x * jnp.sqrt(jnp.array(cfg.d_model, jnp.float32)).astype(x.dtype)
    return x


def _head(params, cfg: ModelConfig, h):
    if cfg.tie_embeddings:
        logits = h @ params["embed"].T
    else:
        logits = h @ params["lm_head"]
    logits = logits.astype(jnp.float32)
    if cfg.final_logit_softcap:
        logits = layers._softcap(logits, cfg.final_logit_softcap)
    return logits


# ---------------------------------------------------------------------------
# backbone (full sequence)
# ---------------------------------------------------------------------------

def backbone(params, cfg: ModelConfig, batch: Dict[str, Any], *, mesh=None,
             collect_cache: bool = False, collect_stages: bool = False):
    """Full-sequence forward.  Returns (hidden, aux_loss, caches, stages).

    ``stages`` (when requested): (n_stages, B, S, D) per-group hidden
    states — the representation stages consumed by the VAA distiller.
    """
    at = cfg.arch_type
    caches: Dict[str, Any] = {}
    stages = None

    if at in ("dense", "moe"):
        tokens = batch["tokens"]
        B, S = tokens.shape
        positions = jnp.arange(S)[None].repeat(B, 0)
        x = shard_act(_embed(params, cfg, tokens), mesh)
        aux = jnp.zeros((), jnp.float32)
        if "dense_blocks" in params:
            dense_cfg = cfg  # same attention; dense FFN chosen by params
            x, a, c, _ = _run_stack(params["dense_blocks"], dense_cfg, x,
                                    positions, pattern=("full",), mesh=mesh,
                                    causal=True, collect_cache=collect_cache)
            aux += a
            caches["dense_blocks"] = c
        x, a, c, stages = _run_stack(params["blocks"], cfg, x, positions,
                                     pattern=cfg.attn_pattern, mesh=mesh,
                                     causal=True, collect_cache=collect_cache,
                                     collect_stages=collect_stages)
        aux += a
        caches["blocks"] = c
        h = layers.apply_norm(params["final_norm"], x, cfg.norm_eps)
        return h, aux, caches, stages

    if at == "vlm":
        tokens = batch["tokens"]
        patches = batch["patches"]  # (B, P, D) precomputed (stub frontend)
        B, S_txt = tokens.shape
        x_txt = _embed(params, cfg, tokens)
        x = jnp.concatenate([patches.astype(x_txt.dtype), x_txt], axis=1)
        S = x.shape[1]
        positions = jnp.arange(S)[None].repeat(B, 0)
        x = shard_act(x, mesh)
        x, aux, c, stages = _run_stack(params["blocks"], cfg, x, positions,
                                       pattern=cfg.attn_pattern, mesh=mesh,
                                       causal=True, collect_cache=collect_cache,
                                       collect_stages=collect_stages)
        caches["blocks"] = c
        h = layers.apply_norm(params["final_norm"], x, cfg.norm_eps)
        return h, aux, caches, stages  # caller slices off patch positions

    if at == "ssm":
        tokens = batch["tokens"]
        x = shard_act(_embed(params, cfg, tokens), mesh)

        def body(x, inp):
            bp = inp
            blk = _maybe_remat(cfg, lambda xx: xx + (
                ssm.ssm_forward(bp["mixer"], cfg,
                                layers.apply_norm(bp["ln"], xx, cfg.norm_eps))))
            x = blk(x)
            x = shard_act(x, mesh)
            return x, (x if collect_stages else 0)

        if collect_cache:
            def body_c(x, bp):
                out, c = ssm.ssm_forward(bp["mixer"], cfg,
                                         layers.apply_norm(bp["ln"], x, cfg.norm_eps),
                                         return_cache=True)
                x = shard_act(x + out, mesh)
                return x, (c, x if collect_stages else 0)
            x, (c, stages) = _scan(cfg, body_c, x, params["blocks"])
            caches["blocks"] = c
        else:
            x, stages = _scan(cfg, body, x, params["blocks"])
        h = layers.apply_norm(params["final_norm"], x, cfg.norm_eps)
        if not collect_stages:
            stages = None
        return h, jnp.zeros((), jnp.float32), caches, stages

    if at == "hybrid":
        return _hybrid_backbone(params, cfg, batch, mesh=mesh,
                                collect_cache=collect_cache,
                                collect_stages=collect_stages)

    if at == "encdec":
        return _encdec_backbone(params, cfg, batch, mesh=mesh,
                                collect_cache=collect_cache,
                                collect_stages=collect_stages)

    raise ValueError(at)


def _hybrid_backbone(params, cfg: ModelConfig, batch, *, mesh, collect_cache,
                     collect_stages: bool = False):
    tokens = batch["tokens"]
    B, S = tokens.shape
    positions = jnp.arange(S)[None].repeat(B, 0)
    x = shard_act(_embed(params, cfg, tokens), mesh)
    caches: Dict[str, Any] = {"attn": [], "mamba": None, "tail": None}
    shared = params["shared_attn"]

    def mamba_scan(x, stack, collect):
        if collect:
            def body(x, bp):
                out, c = ssm.ssm_forward(bp["mixer"], cfg,
                                         layers.apply_norm(bp["ln"], x, cfg.norm_eps),
                                         return_cache=True)
                return x + out, c
            return _scan(cfg, body, x, stack)
        def body(x, bp):
            # nested remat: the outer group checkpoint recomputes this
            # forward during backward; the inner per-block checkpoint then
            # bounds the live set to ONE block's intermediates (§Perf Z2)
            fn = _maybe_remat(cfg, lambda xx: xx + ssm.ssm_forward(
                bp["mixer"], cfg, layers.apply_norm(bp["ln"], xx, cfg.norm_eps)))
            return fn(x), 0
        return _scan(cfg, body, x, stack)

    n_groups = jax.tree.leaves(params["mamba_groups"])[0].shape[0]

    # GROUP-level remat: one residual checkpoint per (shared-attn + period
    # mamba blocks) group — 13 saved boundaries instead of 78+attn for
    # zamba2-7b; see EXPERIMENTS.md §Perf iteration Z1.
    def group_fn(x, gp):
        x, a, kv = _block_full(shared, cfg, x, positions, kind="full",
                               mesh=mesh, causal=True)
        x, mc = mamba_scan(x, gp, collect_cache)
        return x, (kv if collect_cache else 0, mc)

    if not collect_cache:
        group_fn = _maybe_remat(cfg, group_fn)

    def outer_body(x, gp):
        x, (kv, mc) = group_fn(x, gp)
        return x, (kv, mc, x if collect_stages else 0)

    x, (kvs, mcs, stages) = _scan(cfg, outer_body, x, params["mamba_groups"])
    if collect_cache:
        caches["attn"] = kvs
        caches["mamba"] = mcs
    if "mamba_tail" in params:
        x, a, kv = _block_full(shared, cfg, x, positions, kind="full",
                               mesh=mesh, causal=True)
        x, tc = mamba_scan(x, params["mamba_tail"], collect_cache)
        if collect_cache:
            caches["tail_attn"] = kv
            caches["tail"] = tc
    h = layers.apply_norm(params["final_norm"], x, cfg.norm_eps)
    if not collect_stages:
        stages = None
    return h, jnp.zeros((), jnp.float32), caches, stages


def _encdec_backbone(params, cfg: ModelConfig, batch, *, mesh, collect_cache,
                     collect_stages: bool = False):
    frames = batch["frames"]          # (B, T_a, D) stub audio embeddings
    tokens = batch["tokens"]
    B, S = tokens.shape
    Ta = frames.shape[1]
    # --- encoder (bidirectional) ---
    enc_pos = jnp.arange(Ta)[None].repeat(B, 0)
    xe = frames.astype(_dtype(cfg))
    if cfg.pos_embedding == "sinusoidal":
        xe = xe + layers.sinusoidal_positions(enc_pos, cfg.d_model).astype(xe.dtype)
    xe = shard_act(xe, mesh)

    def enc_body(x, bp):
        fn = _maybe_remat(cfg, lambda xx: _block_full(
            bp, cfg, xx, enc_pos, kind="full", mesh=mesh, causal=False)[0])
        return fn(x), 0

    xe, _ = _scan(cfg, enc_body, xe, params["enc_blocks"])
    memory = layers.apply_norm(params["enc_norm"], xe, cfg.norm_eps)

    # --- decoder ---
    dec_pos = jnp.arange(S)[None].repeat(B, 0)
    x = _embed(params, cfg, tokens)
    if cfg.pos_embedding == "sinusoidal":
        x = x + layers.sinusoidal_positions(dec_pos, cfg.d_model).astype(x.dtype)
    x = shard_act(x, mesh)

    def dec_body(x, bp):
        def fn(xx):
            h = layers.apply_norm(bp["ln1"], xx, cfg.norm_eps)
            a, kv = layers.attention_full(bp["attn"], cfg, h, dec_pos,
                                          window=0, causal=True)
            xx = xx + a
            # cross attention
            h = layers.apply_norm(bp["ln_x"], xx, cfg.norm_eps)
            with jax.named_scope(scopes.ATTENTION):
                q, _, _ = layers.attention_qkv(bp["xattn"], cfg, h, dec_pos)
                _, mk, mv = layers.attention_qkv(bp["xattn"], cfg, memory,
                                                 enc_pos)
                xa = layers.chunked_attention(
                    q, mk, mv, dec_pos, enc_pos, causal=False,
                    q_chunk=cfg.attn_chunk_q, k_chunk=cfg.attn_chunk_k,
                    unroll=cfg.scan_unroll)
                xa = xa.reshape(B, S, -1) @ bp["xattn"]["wo"]
            xx = xx + xa
            h = layers.apply_norm(bp["ln2"], xx, cfg.norm_eps)
            with jax.named_scope(scopes.MLP):
                xx = xx + layers.apply_mlp(bp["mlp"], cfg, h)
            # dict layout matches init_decode_cache so prefill_into_cache
            # can graft the decoder self-KV (kv is attention_full's tuple)
            return xx, {"self": {"k": kv[0], "v": kv[1]},
                        "cross": {"k": mk, "v": mv}}
        if cfg.remat:
            fn = jax.checkpoint(fn)
        xx, c = fn(x)
        return xx, (c if collect_cache else 0, xx if collect_stages else 0)

    x, (dec_caches, stages) = _scan(cfg, dec_body, x, params["dec_blocks"])
    h = layers.apply_norm(params["final_norm"], x, cfg.norm_eps)
    caches = {}
    if collect_cache:
        caches = {"self": dec_caches["self"], "cross": dec_caches["cross"],
                  "memory": memory}
    if not collect_stages:
        stages = None
    return h, jnp.zeros((), jnp.float32), caches, stages


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------

@scopes.scoped(scopes.HEAD)
def chunked_ce(params, cfg: ModelConfig, h, labels, mask):
    """Sequence-chunked CE: never materialises (B, S, V) logits at once.

    Returns (sum_nll, sum_tokens, sum_correct) as f32 scalars.
    """
    B, S, D = h.shape
    C = min(cfg.loss_chunk, S)
    pad = (-S) % C
    if pad:
        h = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    n = h.shape[1] // C
    hc = h.reshape(B, n, C, D).transpose(1, 0, 2, 3)
    lc = labels.reshape(B, n, C).transpose(1, 0, 2)
    mc = mask.reshape(B, n, C).transpose(1, 0, 2)

    def body(carry, inp):
        nll_s, tok_s, cor_s = carry
        hh, ll, mm = inp
        if cfg.use_pallas:
            from repro.kernels.kd_loss import ops as kd_ops
            w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
            nll, correct = kd_ops.ce_from_hidden(hh, w, ll,
                                                 softcap=cfg.final_logit_softcap)
        else:
            logits = _head(params, cfg, hh)
            lse = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(logits, ll[..., None], axis=-1)[..., 0]
            nll = lse - gold
            correct = (jnp.argmax(logits, -1) == ll).astype(jnp.float32)
        mmf = mm.astype(jnp.float32)
        return (nll_s + jnp.sum(nll * mmf), tok_s + jnp.sum(mmf),
                cor_s + jnp.sum(correct * mmf)), 0

    body = _maybe_remat(cfg, body) if cfg.remat else body
    (nll, tok, cor), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32),) * 3, (hc, lc, mc),
        unroll=_unroll(cfg, hc))
    return nll, tok, cor


def loss_fn(params, cfg: ModelConfig, batch, *, mesh=None):
    """Autoregressive LM loss (Eq. 2).  Returns (loss, metrics)."""
    h, aux, _, _ = backbone(params, cfg, batch, mesh=mesh)
    labels = batch["labels"]
    mask = batch.get("mask", jnp.ones_like(labels, jnp.float32))
    if cfg.arch_type == "vlm":  # drop patch positions
        h = h[:, -labels.shape[1]:]
    nll, tok, cor = chunked_ce(params, cfg, h, labels, mask)
    loss = nll / jnp.maximum(tok, 1.0)
    metrics = {"nll": nll, "tokens": tok, "accuracy": cor / jnp.maximum(tok, 1.0),
               "aux_loss": aux, "ce_loss": loss}
    if cfg.n_mtp and "mtp" in params:
        mtp_loss = _mtp_loss(params, cfg, h, batch)
        metrics["mtp_loss"] = mtp_loss
        loss = loss + cfg.mtp_loss_weight * mtp_loss
    return loss + aux, metrics


def expert_load(params, cfg: ModelConfig, batch):
    """(expert layers, n_experts) int32: how many of the batch's T*top_k
    assignments each expert of each expert layer of a dense/moe decoder
    receives, in a forward pass on one device."""
    tokens = batch["tokens"]
    B, S = tokens.shape
    positions = jnp.arange(S)[None].repeat(B, 0)
    x = _embed(params, cfg, tokens)
    if "dense_blocks" in params:
        x, _, _, _ = _run_stack(params["dense_blocks"], cfg, x, positions,
                                pattern=("full",), mesh=None, causal=True,
                                collect_cache=False)

    def body(x, gp):
        counts = []
        for i, kind in enumerate(cfg.attn_pattern):
            p = gp[f"sub{i}"]
            x, _ = _block_attn(p, cfg, x, positions, kind=kind)
            h = layers.apply_norm(p["ln2"], x, cfg.norm_eps)
            _, idx, _ = moe.route(p["moe"], cfg, h.reshape(B * S, -1),
                                  n_seq=B)
            counts.append(jnp.bincount(idx.reshape(-1),
                                       length=cfg.n_experts))
            x, _ = _block_ffn(p, cfg, x, mesh=None)
        return x, jnp.stack(counts)

    _, counts = _scan(cfg, body, x, params["blocks"])
    return counts.reshape(-1, cfg.n_experts).astype(jnp.int32)


def _mtp_loss(params, cfg: ModelConfig, h, batch):
    """DeepSeek-V3 multi-token prediction head (depth 1): predict t+2."""
    tokens, labels = batch["tokens"], batch["labels"]
    B, S = tokens.shape
    mp = params["mtp"]
    # combine hidden at t with embedding of token t+1
    emb_next = _embed(params, cfg, jnp.roll(tokens, -1, axis=1))
    hin = jnp.concatenate([layers.apply_norm(mp["norm"], h, cfg.norm_eps),
                           emb_next.astype(h.dtype)], axis=-1) @ mp["proj"]
    positions = jnp.arange(S)[None].repeat(B, 0)
    hout, _, _ = _block_full(mp["block"], cfg, hin, positions, kind="full",
                             mesh=None)
    labels2 = jnp.roll(labels, -1, axis=1)
    mask = jnp.ones_like(labels2, jnp.float32).at[:, -2:].set(0.0)
    nll, tok, _ = chunked_ce(params, cfg, hout, labels2, mask)
    return nll / jnp.maximum(tok, 1.0)


def mtp_chain_loss(params, cfg: ModelConfig, batch, *, depth: int,
                   mesh=None):
    """Teacher-forced CHAINED MTP loss: supervise the draft head at every
    chain depth ``1..depth``, feeding its own output hidden back in —
    exactly how ``_mtp_draft`` chains at inference.  ``_mtp_loss`` only
    trains depth 1 from backbone hiddens, so a head trained with it
    alone degrades sharply past the first speculative draft; train with
    this when serving with ``speculate > 1``.  Tokens are teacher-forced
    (ground truth at every depth) — on sequences the drafter gets right
    this matches the on-policy inference distribution.

    Depth j at position i combines the depth j-1 hidden with the
    embedding of token i+j and predicts token i+j+1; the last j+1
    positions roll around and are masked out.  Returns the mean NLL
    averaged over depths (depth 1 reproduces ``_mtp_loss`` exactly).
    """
    tokens, labels = batch["tokens"], batch["labels"]
    B, S = tokens.shape
    mp = params["mtp"]
    h, _, _, _ = backbone(params, cfg, batch, mesh=mesh)
    positions = jnp.arange(S)[None].repeat(B, 0)
    total = jnp.zeros((), jnp.float32)
    for j in range(1, depth + 1):
        emb = _embed(params, cfg, jnp.roll(tokens, -j, axis=1))
        hin = jnp.concatenate([layers.apply_norm(mp["norm"], h, cfg.norm_eps),
                               emb.astype(h.dtype)], axis=-1) @ mp["proj"]
        h, _, _ = _block_full(mp["block"], cfg, hin, positions, kind="full",
                              mesh=mesh)
        lab = jnp.roll(labels, -j, axis=1)
        mask = jnp.ones_like(lab, jnp.float32).at[:, -(j + 1):].set(0.0)
        nll, tok, _ = chunked_ce(params, cfg, h, lab, mask)
        total = total + nll / jnp.maximum(tok, 1.0)
    return total / depth


# ---------------------------------------------------------------------------
# serving: prefill + decode
# ---------------------------------------------------------------------------

def prefill(params, cfg: ModelConfig, batch, *, mesh=None,
            return_hidden=False):
    """Runs the full prompt, returns (last_token_logits, cache).

    ``return_hidden`` packs the last position's pre-head hidden next to
    the logits — ``((logits, h_last), cache)`` — so a speculative
    engine can seed its first draft chain hot instead of burning the
    admission step's drafts on a zero hidden.
    """
    h, _, caches, _ = backbone(params, cfg, batch, mesh=mesh,
                               collect_cache=True)
    with jax.named_scope(scopes.HEAD):
        logits = _head(params, cfg, h[:, -1:])[:, 0]
    if return_hidden:
        return (logits, h[:, -1]), caches
    return logits, caches


def _place_tree(tree, mesh, spec_tree):
    """Lay a freshly-built cache tree out over ``mesh`` per the rules'
    PartitionSpecs.  ``mesh=None`` (or a trivial 1-device mesh) is a
    no-op, so single-device layouts stay bit-identical."""
    if mesh is None or mesh.size == 1:
        return tree
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        tree, spec_tree)


def init_decode_cache(cfg: ModelConfig, B: int, S: int, mesh=None,
                      policy=None):
    """Zeroed cache pytree for ``decode_step`` (capacity S).

    ``policy`` (a ``quant.CachePolicy``) names the storage dtype of the
    SELF-attention KV leaves; quantized policies add per-row float32
    ``_scale`` siblings.  Recurrent state (ssm/hybrid), encdec cross KV
    and encoder memory opt out — they are read linearly every step, so
    quantizing them buys little and costs accuracy.  ``policy=None``
    keeps the historical param-dtype layout bit-for-bit.

    With ``mesh`` the cache is laid out with ``NamedSharding`` per
    ``sharding.rules.cache_specs`` — slot (batch) axes over the data
    axes, sequence over "model" where divisible — instead of living on
    one device.  ``mesh=None`` / 1-device meshes are unchanged.
    """
    dtype = _dtype(cfg)
    at = cfg.arch_type
    if mesh is not None and mesh.size > 1:
        from repro.sharding import rules
        tree = init_decode_cache(cfg, B, S, policy=policy)
        specs = rules.cache_specs(tree, mesh, batch=B, seq=S)
        return _place_tree(tree, mesh, specs)

    def attn_entry():
        return _attn_cache_struct(cfg, B, S, dtype, policy)

    def stack(entry, n):
        return jax.tree.map(lambda x: jnp.broadcast_to(x, (n,) + x.shape), entry)

    if at in ("dense", "moe", "vlm"):
        lps = cfg.layers_per_scan
        n_groups = (cfg.n_layers - cfg.first_dense_layers) // lps
        c = {"blocks": stack({f"sub{i}": attn_entry() for i in range(lps)},
                             n_groups)}
        if cfg.first_dense_layers:
            c["dense_blocks"] = stack({"sub0": attn_entry()},
                                      cfg.first_dense_layers)
        return c
    if at == "ssm":
        entry = {"state": jnp.zeros((B, cfg.ssm_heads, cfg.ssm_head_dim,
                                     cfg.ssm_state), jnp.float32),
                 "conv": jnp.zeros((B, cfg.ssm_conv - 1,
                                    cfg.d_inner + 2 * cfg.ssm_groups * cfg.ssm_state),
                                   dtype)}
        return {"blocks": stack(entry, cfg.n_layers)}
    if at == "hybrid":
        period = cfg.shared_attn_every
        n_groups, tail = divmod(cfg.n_layers, period)
        entry = {"state": jnp.zeros((B, cfg.ssm_heads, cfg.ssm_head_dim,
                                     cfg.ssm_state), jnp.float32),
                 "conv": jnp.zeros((B, cfg.ssm_conv - 1,
                                    cfg.d_inner + 2 * cfg.ssm_groups * cfg.ssm_state),
                                   dtype)}
        c = {"mamba": stack(stack(entry, period), n_groups),
             "attn": stack(attn_entry(), n_groups + (1 if tail else 0))}
        if tail:
            c["tail"] = stack(entry, tail)
        return c
    if at == "encdec":
        self_entry = stack(attn_entry(), cfg.n_layers)
        cross = stack(_attn_cache_struct(cfg, B, cfg.frontend_tokens, dtype),
                      cfg.n_layers)
        return {"self": self_entry, "cross": cross,
                "memory": jnp.zeros((B, cfg.frontend_tokens, cfg.d_model), dtype)}
    raise ValueError(at)


def decode_offset(cfg: ModelConfig) -> int:
    """Leading cache positions occupied by the modality frontend.

    VLM prompts are ``[patches | text]``: the prefill cache stores patch
    rows first, so text decode positions start at ``frontend_tokens``.
    Every other family decodes from position ``prompt_len`` directly
    (the encdec frontend lives in the separate cross/memory entries).
    """
    return cfg.frontend_tokens if cfg.arch_type == "vlm" else 0


def decode_capacity(cfg: ModelConfig, prompt_len: int, max_new: int) -> int:
    """Exact decode-cache capacity for a prompt + ``max_new`` generated
    tokens (the first of which is sampled from the prefill logits)."""
    return decode_offset(cfg) + prompt_len + max_new


def decode_pos0(cfg: ModelConfig, prompt_len: int) -> int:
    """First decode position after a ``prompt_len``-token prefill."""
    return decode_offset(cfg) + prompt_len


def graft_cache_entry(dst, src):
    """Copy a prefill cache entry into a (same-or-larger) decode entry.

    Exactly one dim (the sequence axis) may differ between the decode
    and prefill entries; anything else is a caller bug and raises.
    """
    if dst.shape == src.shape:
        return src.astype(dst.dtype)
    diff = [ax for ax, (a, b) in enumerate(zip(dst.shape, src.shape))
            if a != b]
    if dst.ndim != src.ndim or len(diff) != 1:
        raise ValueError(
            f"graft_cache_entry: decode cache {dst.shape} and prefill cache "
            f"{src.shape} differ in more than one dim — the caches were "
            f"built for different batch/model shapes")
    ax = diff[0]
    if src.shape[ax] > dst.shape[ax]:
        raise ValueError(
            f"graft_cache_entry: prefill length {src.shape[ax]} exceeds "
            f"decode cache capacity {dst.shape[ax]} (axis {ax})")
    idx = [slice(None)] * dst.ndim
    idx[ax] = slice(0, src.shape[ax])
    return dst.at[tuple(idx)].set(src.astype(dst.dtype))


def prefill_into_cache(cfg: ModelConfig, decode_cache, prefill_cache):
    """Align a ``prefill`` cache into a ``decode_step`` cache.

    The ONE place that knows the cache layout per arch family:

      dense/moe : graft ``blocks`` (+ leading ``dense_blocks``) along the
                  sequence axis of each stacked KV / MLA-latent entry.
      vlm       : same — the prefill entries already contain the patch
                  rows, so the graft lands on ``[0, frontend_tokens + P)``
                  and decode positions continue at ``decode_pos0``.
      ssm       : recurrent state/conv tails are position-free; adopt.
      hybrid    : adopt mamba state; graft the per-group shared-attn KV;
                  fold the separately-stored ``tail_attn`` entry into the
                  last row of the stacked ``attn`` cache.
      encdec    : graft decoder ``self`` KV; adopt the fixed-length
                  ``cross`` KV and encoder ``memory``.
    """
    at = cfg.arch_type
    if at in ("dense", "moe", "vlm"):
        out = {"blocks": jax.tree.map(graft_cache_entry,
                                      decode_cache["blocks"],
                                      prefill_cache["blocks"])}
        if "dense_blocks" in decode_cache:
            out["dense_blocks"] = jax.tree.map(graft_cache_entry,
                                               decode_cache["dense_blocks"],
                                               prefill_cache["dense_blocks"])
        return out
    if at == "ssm":
        return jax.tree.map(graft_cache_entry, decode_cache, prefill_cache)
    if at == "hybrid":
        pc = {k: v for k, v in prefill_cache.items() if v is not None}
        out = {"mamba": jax.tree.map(graft_cache_entry,
                                     decode_cache["mamba"], pc["mamba"])}
        has_tail = "tail" in decode_cache
        if has_tail:
            n_groups = jax.tree.leaves(pc["attn"])[0].shape[0]

            def fold(dst, src, tail):
                body = graft_cache_entry(dst[:n_groups], src)
                return dst.at[:n_groups].set(body).at[-1].set(
                    graft_cache_entry(dst[-1], tail))

            out["attn"] = jax.tree.map(fold, decode_cache["attn"],
                                       pc["attn"], pc["tail_attn"])
            out["tail"] = jax.tree.map(graft_cache_entry,
                                       decode_cache["tail"], pc["tail"])
        else:
            out["attn"] = jax.tree.map(graft_cache_entry,
                                       decode_cache["attn"], pc["attn"])
        return out
    if at == "encdec":
        return {"self": jax.tree.map(graft_cache_entry,
                                     decode_cache["self"],
                                     prefill_cache["self"]),
                "cross": jax.tree.map(graft_cache_entry,
                                      decode_cache["cross"],
                                      prefill_cache["cross"]),
                "memory": graft_cache_entry(decode_cache["memory"],
                                            prefill_cache["memory"])}
    raise ValueError(at)


def decode_cache_batch_axes(cfg: ModelConfig, policy=None):
    """Tree of the batch-axis index of every decode-cache leaf.

    The batch axis sits behind a varying number of stacked layer axes
    (e.g. hybrid mamba state is (groups, period, B, ...)); discover it by
    diffing two abstract caches that differ only in B.  ``policy`` must
    match the cache being indexed — quantized policies add ``_scale``
    leaves, and the axes tree must mirror that structure.
    """
    a = jax.eval_shape(lambda: init_decode_cache(cfg, 2, 8, policy=policy))
    b = jax.eval_shape(lambda: init_decode_cache(cfg, 3, 8, policy=policy))

    def axis(x, y):
        return next(i for i, (p, q) in enumerate(zip(x.shape, y.shape))
                    if p != q)

    return jax.tree.map(axis, a, b)


# ---------------------------------------------------------------------------
# serving: block-paged decode cache
# ---------------------------------------------------------------------------

def decode_cache_seq_axes(cfg: ModelConfig, policy=None):
    """Tree of the sequence-axis index of every decode-cache leaf, or -1
    for leaves with no growing sequence axis (ssm state/conv, encdec
    cross KV and encoder memory).  Discovered by diffing two abstract
    caches that differ only in S — the -1 leaves are exactly the ones
    that stay slot-resident under the paged layout."""
    a = jax.eval_shape(lambda: init_decode_cache(cfg, 2, 8, policy=policy))
    b = jax.eval_shape(lambda: init_decode_cache(cfg, 2, 16, policy=policy))

    def axis(x, y):
        diff = [i for i, (p, q) in enumerate(zip(x.shape, y.shape)) if p != q]
        return diff[0] if diff else -1

    return jax.tree.map(axis, a, b)


def has_paged_leaves(cfg: ModelConfig) -> bool:
    """False only for families whose whole decode state is per-slot
    recurrent (pure ssm) — the paged engine then degenerates to the
    contiguous one with no block pool to manage."""
    return any(ax >= 0 for ax in jax.tree.leaves(decode_cache_seq_axes(cfg)))


def init_paged_cache(cfg: ModelConfig, n_slots: int, n_blocks: int,
                     block_len: int, mesh=None, policy=None):
    """Block-paged decode cache.

    Sequence-carrying leaves become per-leaf block pools: the contiguous
    (stacked_layers..., B, S, ...) leaf turns into (stacked_layers...,
    n_blocks, block_len, ...) — block id b is row b of EVERY pool, so one
    allocator id spans all layers (vLLM-style).  Leaves with no sequence
    axis (ssm/hybrid recurrent state, encdec cross KV + memory) keep
    their per-slot batch axis of ``n_slots``.  Block 0 is the trash
    block: never allocated, it absorbs the masked writes of finished
    slots (see ``repro.serve.paged``).

    With ``mesh`` the layout follows ``sharding.rules.paged_cache_specs``:
    each device owns a contiguous shard of every block pool (the
    allocator's per-shard free lists mirror this split) and pool feature
    dims shard over "model"; slot-resident leaves shard their slot axis
    over the data axes.  ``mesh=None`` / 1-device meshes are unchanged.
    """
    pool = init_decode_cache(cfg, n_blocks, block_len, policy=policy)
    slotted = init_decode_cache(cfg, n_slots, block_len, policy=policy)
    seq = decode_cache_seq_axes(cfg, policy=policy)
    tree = jax.tree.map(lambda p, s, ax: p if ax >= 0 else s,
                        pool, slotted, seq)
    if mesh is not None and mesh.size > 1:
        from repro.sharding import rules
        specs = rules.paged_cache_specs(
            tree, mesh,
            batch_axes=decode_cache_batch_axes(cfg, policy=policy),
            seq_axes=seq)
        return _place_tree(tree, mesh, specs)
    return tree


def match_cache_policy(template, sub):
    """Re-structure a full-precision cache ``sub`` to the (possibly
    quantized) ``template``'s policy: data leaves with a ``_scale``
    sibling in the template are quantized along their trailing feature
    axis (write-time scales); everything else passes through.  A
    no-op (identity structure) for unquantized templates."""
    pol = quant.policy_of(template)
    if not pol.quantized:
        return sub

    def walk(tmpl, src):
        if not isinstance(tmpl, dict):
            return src
        out = {}
        for key, tval in tmpl.items():
            if isinstance(key, str) and quant.is_scale_key(key):
                continue
            if isinstance(tval, dict):
                out[key] = walk(tval, src[key])
            elif isinstance(key, str) and quant.scale_name(key) in tmpl:
                q, s = quant.quantize(src[key], pol.kv_dtype)
                out[key] = q
                out[quant.scale_name(key)] = s
            else:
                out[key] = src[key]
        return out

    return walk(template, sub)


def scatter_prefill_paged(cfg: ModelConfig, paged_cache, sub, slot, ids,
                          mask, *, block_len: int):
    """Scatter a B=1 contiguous decode cache ``sub`` (already grafted via
    ``prefill_into_cache``, S = len(ids) * block_len) into the paged
    cache: paged leaves land in pool blocks ``ids`` (n_prompt_blocks,),
    slot-resident leaves in batch row ``slot``.  ``mask`` (same shape as
    ``ids``) is False for blocks whose content is already pooled (prefix
    sharing) — their writes are diverted to the trash block 0 instead of
    re-writing (identical) shared content.

    ``sub`` is always the full-precision prefill graft; when the paged
    cache is quantized, KV leaves are quantized here (per-row scales
    computed at write time) so pool content is a pure function of the
    written tokens — the invariant prefix sharing relies on."""
    pol = quant.policy_of(paged_cache)
    bat = decode_cache_batch_axes(cfg, policy=pol)
    seq = decode_cache_seq_axes(cfg, policy=pol)
    sub = match_cache_policy(paged_cache, sub)
    ids_eff = jnp.where(mask, ids, 0)

    def put(dst, src, bax, sax):
        if sax < 0:
            idx = [slice(None)] * dst.ndim
            idx[bax] = slot
            return dst.at[tuple(idx)].set(
                jnp.take(src, 0, axis=bax).astype(dst.dtype))
        s = jnp.take(src, 0, axis=bax)  # drop B; seq axis now sits at bax
        s = s.reshape(s.shape[:bax] + (-1, block_len) + s.shape[bax + 1:])
        s = jnp.moveaxis(s, bax, 0)     # (n_prompt_blocks, L..., bl, T...)
        d = jnp.moveaxis(dst, bax, 0)   # (n_blocks, L..., bl, T...)
        d = d.at[ids_eff].set(s.astype(d.dtype))
        return jnp.moveaxis(d, 0, bax)

    return jax.tree.map(put, paged_cache, sub, bat, seq)


def cache_nbytes(cfg: ModelConfig, B: int, S: int, policy=None) -> int:
    """Bytes of a contiguous (B, S) decode cache (abstract, no alloc).

    Summed per leaf at each leaf's OWN itemsize — under a quantized
    policy the cache mixes int8/fp8 KV leaves with float32 scale (and
    opted-out recurrent) leaves, so a single-itemsize estimate would
    misprice every equal-bytes comparison."""
    tree = jax.eval_shape(lambda: init_decode_cache(cfg, B, S,
                                                    policy=policy))
    return sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(tree))


def paged_cache_nbytes(cfg: ModelConfig, n_slots: int, n_blocks: int,
                       block_len: int, policy=None) -> int:
    """Bytes of the paged cache: block pools + slot-resident leaves,
    summed per leaf at each leaf's own itemsize (see cache_nbytes)."""
    tree = jax.eval_shape(
        lambda: init_paged_cache(cfg, n_slots, n_blocks, block_len,
                                 policy=policy))
    return sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(tree))


def _overlap_ok(cfg: ModelConfig, mesh, B: int, block_tables) -> bool:
    """Gate for the EP-A2A overlapped decode step.

    Contiguous-cache MoE decode on a multi-device "model" axis only, and
    the batch must split into two equal halves.  Paged caches are
    excluded: both halves would scatter into the SAME trash block row,
    and merging the two written pools is not expressible as a concat.
    """
    if not (cfg.overlap_a2a and cfg.is_moe and block_tables is None):
        return False
    if cfg.moe_impl not in ("auto", "a2a"):
        return False
    if mesh is None or "model" not in mesh.axis_names:
        return False
    return mesh.shape["model"] > 1 and B >= 2 and B % 2 == 0


def _decode_step_overlapped(params, cfg: ModelConfig, cache, x, pos, *,
                            mesh, live):
    """Batch-level EP-A2A overlap (Megatron-Core style): run the decode
    body on two independent batch halves, each with its own cache slice.
    The halves share no data flow, so XLA's latency-hiding scheduler can
    run half 0's MoE ``all_to_all`` concurrently with half 1's attention
    compute (asserted at the HLO level by
    ``launch.hlo_analysis.assert_a2a_overlap``).

    Expert capacity is computed per half (over B/2 rows), so this is NOT
    bitwise-identical to the unsplit step when drops occur; at serving
    batch sizes the per-half capacity ceil is the same and outputs match
    (the sharded identity tests exercise exactly this).
    """
    B = x.shape[0]
    half = B // 2
    bat = decode_cache_batch_axes(cfg, policy=quant.policy_of(cache))

    def run(lo, hi):
        c = jax.tree.map(
            lambda leaf, ax: jax.lax.slice_in_dim(leaf, lo, hi, axis=ax),
            cache, bat)
        lv = None if live is None else live[lo:hi]
        return _chunk_hidden(params, cfg, c, x[lo:hi], pos[lo:hi],
                             mesh=mesh, live=lv)

    h0, nc0 = run(0, half)
    h1, nc1 = run(half, B)
    h = jnp.concatenate([h0, h1], axis=0)
    new_cache = jax.tree.map(
        lambda a, b, ax: jnp.concatenate([a, b], axis=ax), nc0, nc1, bat)
    return h, new_cache


def decode_step(params, cfg: ModelConfig, cache, tokens, pos, *, mesh=None,
                block_tables=None, live=None):
    """One serving step: tokens (B, 1) at positions pos (B,).

    With ``block_tables`` (B, nbt) the cache is the paged layout of
    ``init_paged_cache``: sequence-carrying leaves are block pools read
    through the table; slot-resident leaves (ssm state, encdec
    cross/memory) are indexed by batch row exactly as before.

    ``live`` (B,) bool marks rows holding real requests; freed engine
    slots are masked out of MoE routing and expert-capacity accounting
    (``live=None`` treats every row as live — bit-identical to the
    pre-mask behavior).

    Returns (logits (B, V), new_cache).  This is the C=1 case of the
    shared ``_chunk_hidden`` body that chunked prefill feeds C-token
    chunks through.
    """
    x = _embed(params, cfg, tokens)
    lv = None if live is None else live[:, None]
    if _overlap_ok(cfg, mesh, x.shape[0], block_tables):
        h, new_cache = _decode_step_overlapped(params, cfg, cache, x,
                                               pos[:, None], mesh=mesh,
                                               live=lv)
    else:
        h, new_cache = _chunk_hidden(params, cfg, cache, x, pos[:, None],
                                     mesh=mesh, block_tables=block_tables,
                                     live=lv)
    with jax.named_scope(scopes.HEAD):
        return _head(params, cfg, h)[:, 0], new_cache


def _chunk_hidden(params, cfg: ModelConfig, cache, x, pos, *, mesh=None,
                  block_tables=None, write_tables=None, n_valid=None,
                  live=None):
    """Shared decode / chunked-prefill body: pre-embedded inputs x
    (B, C, D) at positions pos (B, C), written into (and attended
    against) the decode cache.  Returns (final-normed hidden (B, C, D),
    new_cache).

    C=1 is the classic decode step.  C>1 is one chunked-prefill chunk:
    attention families need no extra masking (per-query positional
    masks give in-chunk causality, and bucket-pad writes land beyond
    every live query's visibility), but the ssm/hybrid recurrence
    integrates everything it sees, so ``n_valid`` (B,) freezes state
    and conv-tail updates for pad positions (see ssm_prefill_chunk).

    ``live`` (B, C) bool masks dead rows/positions out of MoE routing
    and capacity; when omitted it is derived from ``n_valid`` (bucket
    pads past the real prompt are dead for routing purposes too).
    """
    at = cfg.arch_type
    C = x.shape[1]
    if live is None and n_valid is not None:
        live = jnp.arange(C)[None, :] < n_valid[:, None]

    if at in ("dense", "moe", "vlm"):
        if "dense_blocks" in params:
            x, c0 = _decode_stack(params["dense_blocks"], cfg, x, pos,
                                  cache["dense_blocks"], pattern=("full",),
                                  mesh=mesh, block_tables=block_tables,
                                  write_tables=write_tables, live=live)
        x, c1 = _decode_stack(params["blocks"], cfg, x, pos, cache["blocks"],
                              pattern=cfg.attn_pattern, mesh=mesh,
                              block_tables=block_tables,
                              write_tables=write_tables, live=live)
        new_cache = {"blocks": c1}
        if "dense_blocks" in params:
            new_cache["dense_blocks"] = c0
    elif at == "ssm":
        def body(x, inp):
            bp, bc = inp
            out, nc = _ssm_step(bp, cfg, x, bc, C, n_valid)
            return x + out, nc
        x, nc = _scan(cfg, body, x, (params["blocks"], cache["blocks"]))
        new_cache = {"blocks": nc}
    elif at == "hybrid":
        x, new_cache = _hybrid_decode(params, cfg, x, pos, cache, mesh=mesh,
                                      block_tables=block_tables,
                                      write_tables=write_tables,
                                      n_valid=n_valid, live=live)
    elif at == "encdec":
        x, new_cache = _encdec_decode(params, cfg, x, pos, cache, mesh=mesh,
                                      block_tables=block_tables,
                                      write_tables=write_tables)
    else:
        raise ValueError(at)

    return layers.apply_norm(params["final_norm"], x, cfg.norm_eps), new_cache


def _ssm_step(bp, cfg: ModelConfig, x, bc, C: int, n_valid):
    """One Mamba-2 block: the O(1) recurrence for C=1, the SSD chunk
    path (state + conv carry, pad-frozen via ``n_valid``) for C>1."""
    h = layers.apply_norm(bp["ln"], x, cfg.norm_eps)
    if C == 1:
        return ssm.ssm_decode(bp["mixer"], cfg, h, bc)
    return ssm.ssm_prefill_chunk(bp["mixer"], cfg, h, bc, n_valid)


def _hybrid_decode(params, cfg: ModelConfig, x, pos, cache, *, mesh,
                   block_tables=None, write_tables=None, n_valid=None,
                   live=None):
    shared = params["shared_attn"]
    C = x.shape[1]

    def mamba_body(x, inp):
        bp, bc = inp
        out, nc = _ssm_step(bp, cfg, x, bc, C, n_valid)
        return x + out, nc

    def group_body(x, inp):
        gp, gc, ac = inp
        x, nac = _block_decode(shared, cfg, x, pos, ac, kind="full", mesh=mesh,
                               block_tables=block_tables,
                               write_tables=write_tables, live=live)
        x, ngc = _scan(cfg, mamba_body, x, (gp, gc))
        return x, (ngc, nac)

    n_groups = jax.tree.leaves(params["mamba_groups"])[0].shape[0]
    has_tail = "mamba_tail" in params
    attn_cache = cache["attn"]
    attn_groups = jax.tree.map(lambda t: t[:n_groups], attn_cache)
    x, (nmc, nac) = _scan(
        cfg, group_body, x, (params["mamba_groups"], cache["mamba"], attn_groups))
    new_cache = {"mamba": nmc}
    if has_tail:
        tail_attn = jax.tree.map(lambda t: t[n_groups], attn_cache)
        x, nta = _block_decode(shared, cfg, x, pos, tail_attn, kind="full",
                               mesh=mesh, block_tables=block_tables,
                               write_tables=write_tables, live=live)
        x, ntc = _scan(cfg, mamba_body, x, (params["mamba_tail"], cache["tail"]))
        new_cache["tail"] = ntc
        new_cache["attn"] = jax.tree.map(
            lambda a, b: jnp.concatenate([a, b[None]], 0), nac, nta)
    else:
        new_cache["attn"] = nac
    return x, new_cache


def _encdec_decode(params, cfg: ModelConfig, x, pos, cache, *, mesh,
                   block_tables=None, write_tables=None):
    B, C = x.shape[:2]
    if cfg.pos_embedding == "sinusoidal":
        x = x + layers.sinusoidal_positions(pos, cfg.d_model).astype(x.dtype)

    def body(x, inp):
        bp, sc, cc = inp
        h = layers.apply_norm(bp["ln1"], x, cfg.norm_eps)
        a, nsc = layers.attention_decode(bp["attn"], cfg, h, pos, sc,
                                         window=0,
                                         block_table=block_tables,
                                         write_table=write_tables)
        x = x + a
        h = layers.apply_norm(bp["ln_x"], x, cfg.norm_eps)
        with jax.named_scope(scopes.ATTENTION):
            q, _, _ = layers.attention_qkv(bp["xattn"], cfg, h, pos)
            Ta = cc["k"].shape[1]
            kpos = jnp.arange(Ta)[None].repeat(B, 0)
            xa = layers.decode_attention(q, cc["k"], cc["v"], pos, kpos,
                                         causal=False)
            xa = xa.reshape(B, C, -1) @ bp["xattn"]["wo"]
        x = x + xa
        h = layers.apply_norm(bp["ln2"], x, cfg.norm_eps)
        with jax.named_scope(scopes.MLP):
            x = x + layers.apply_mlp(bp["mlp"], cfg, h)
        return x, nsc

    x, nsc = _scan(cfg, body, x, (params["dec_blocks"], cache["self"],
                                  cache["cross"]))
    return x, {"self": nsc, "cross": cache["cross"], "memory": cache["memory"]}


# ---------------------------------------------------------------------------
# serving: chunked prefill through the decode cache
# ---------------------------------------------------------------------------

def _encdec_encode(params, cfg: ModelConfig, cache, frames, *, mesh):
    """Run the encoder and write ``cross`` KV + ``memory`` into the
    decode cache — the fixed-shape half of an encdec chunked prefill
    (frames are always ``frontend_tokens`` long, so this never forces a
    new executable).  Bit-identical to the ``_encdec_backbone`` path."""
    B, Ta = frames.shape[:2]
    enc_pos = jnp.arange(Ta)[None].repeat(B, 0)
    xe = frames.astype(_dtype(cfg))
    if cfg.pos_embedding == "sinusoidal":
        xe = xe + layers.sinusoidal_positions(enc_pos, cfg.d_model).astype(xe.dtype)
    xe = shard_act(xe, mesh)

    def enc_body(x, bp):
        return _block_full(bp, cfg, x, enc_pos, kind="full", mesh=mesh,
                           causal=False)[0], 0

    xe, _ = _scan(cfg, enc_body, xe, params["enc_blocks"])
    memory = layers.apply_norm(params["enc_norm"], xe, cfg.norm_eps)
    with jax.named_scope(scopes.ATTENTION):
        mk, mv = jax.vmap(lambda bp: layers.attention_qkv(
            bp["xattn"], cfg, memory, enc_pos)[1:])(params["dec_blocks"])
    cache = dict(cache)
    cache["cross"] = {"k": mk.astype(cache["cross"]["k"].dtype),
                      "v": mv.astype(cache["cross"]["v"].dtype)}
    cache["memory"] = memory.astype(cache["memory"].dtype)
    return cache


def prefill_chunked(params, cfg: ModelConfig, cache, batch, prompt_len, *,
                    chunk_len: int, mesh=None, block_tables=None,
                    write_tables=None):
    """Prefill a prompt THROUGH the decode cache in fixed-size chunks.

    ``batch`` is a B-row prefill batch whose ``tokens`` are padded (any
    values) to a bucket length such that the full input sequence —
    ``decode_offset(cfg) + tokens.shape[1]`` — is a multiple of
    ``chunk_len``; ``prompt_len`` (scalar or (B,)) is the TRUE token
    count.  ``cache`` is a decode cache (contiguous, or the paged
    slot-view + pools with ``block_tables`` (B, nbt); the tables must be
    wide enough for every padded position — table gathers clamp, so an
    undersized table would alias its last block).  Each chunk runs the
    shared ``_chunk_hidden`` decode body, so prompt processing and
    decode are ONE code path and the executable depends only on
    (bucket, chunk_len), not the true prompt length.

    Pad positions continue sequentially past the prompt: their
    attention writes land beyond every live query's causal visibility
    (and decode overwrites each position before attending to it), their
    contiguous writes past the cache capacity are dropped by the
    scatter, their paged writes fall through table rows pointing at the
    trash block, and the ssm/hybrid recurrence is explicitly frozen for
    them (``n_valid``).  Recurrent (no-sequence-axis) leaves are zeroed
    first so a reused slot's stale state never leaks into the new
    request.

    Returns (logits of the last real token (B, V), cache).
    """
    at = cfg.arch_type
    tokens = batch["tokens"]
    B, T_pad = tokens.shape
    offset = decode_offset(cfg)
    S_total = offset + T_pad
    if S_total % chunk_len:
        raise ValueError(
            f"padded input length {S_total} (offset {offset} + tokens "
            f"{T_pad}) must be a multiple of chunk_len {chunk_len}")
    seq = decode_cache_seq_axes(cfg, policy=quant.policy_of(cache))
    cache = jax.tree.map(
        lambda leaf, ax: jnp.zeros_like(leaf) if ax < 0 else leaf, cache, seq)
    if at == "encdec":
        cache = _encdec_encode(params, cfg, cache, batch["frames"], mesh=mesh)

    x_full = _embed(params, cfg, tokens)
    if at == "vlm":
        x_full = jnp.concatenate(
            [batch["patches"].astype(x_full.dtype), x_full], axis=1)
    total_real = offset + jnp.broadcast_to(
        jnp.asarray(prompt_len, jnp.int32).reshape(-1), (B,))

    n_chunks = S_total // chunk_len
    D = x_full.shape[-1]
    xs = x_full.reshape(B, n_chunks, chunk_len, D).transpose(1, 0, 2, 3)
    pos_full = jnp.arange(S_total)[None].repeat(B, 0)
    ps = pos_full.reshape(B, n_chunks, chunk_len).transpose(1, 0, 2)

    def body(carry, inp):
        cache, h_last = carry
        x_c, pos_c = inp
        start = pos_c[:, 0]
        n_valid = jnp.clip(total_real - start, 0, chunk_len)
        h, cache = _chunk_hidden(params, cfg, cache, x_c, pos_c, mesh=mesh,
                                 block_tables=block_tables,
                                 write_tables=write_tables, n_valid=n_valid)
        off = total_real - 1 - start
        here = (off >= 0) & (off < chunk_len)
        h_sel = jnp.take_along_axis(
            h, jnp.clip(off, 0, chunk_len - 1)[:, None, None], axis=1)[:, 0]
        h_last = jnp.where(here[:, None], h_sel, h_last)
        return (cache, h_last), 0

    h0 = jnp.zeros((B, D), _dtype(cfg))
    (cache, h_last), _ = jax.lax.scan(body, (cache, h0), (xs, ps))
    with jax.named_scope(scopes.HEAD):
        return _head(params, cfg, h_last[:, None])[:, 0], cache


# ---------------------------------------------------------------------------
# serving: scanned generation
# ---------------------------------------------------------------------------

def greedy_sample(keys, logits):
    """Default sampler: per-slot argmax.  keys (B, 2) ignored."""
    del keys
    return jnp.argmax(logits, -1).astype(jnp.int32)


def greedy_verify(keys, logits, draft):
    """Verify twin of ``greedy_sample``: emit the argmax of the TARGET
    logits at a drafted position; the draft is accepted iff it matches,
    so the emitted stream is exactly the greedy stream."""
    del keys
    tgt = jnp.argmax(logits, -1).astype(jnp.int32)
    return tgt, tgt == draft


def _verify_for(sampler):
    v = getattr(sampler, "verify", None)
    if v is not None:
        return v
    if sampler is greedy_sample:
        return greedy_verify
    raise ValueError(
        "speculative decode needs a sampler with a verify() method "
        "(see repro.serve.sampling)")


def _mtp_draft(params, cfg: ModelConfig, h, tok, pos, *, mesh=None):
    """One inference-time MTP draft: combine the final-normed hidden
    ``h`` (B, D) of the position that emitted ``tok`` (B,) with the
    embedding of ``tok`` — the exact training-time ``_mtp_loss``
    combination — and run the depth-1 MTP block at a single position.

    Returns (draft logits (B, V), hidden for chaining the next draft).
    The draft head reuses the LM head WITHOUT ``final_norm``, matching
    how training feeds the block output straight into ``chunked_ce``.
    """
    mp = params["mtp"]
    emb = _embed(params, cfg, tok[:, None])
    hin = jnp.concatenate([layers.apply_norm(mp["norm"], h[:, None], cfg.norm_eps),
                           emb.astype(h.dtype)], axis=-1) @ mp["proj"]
    hout, _, _ = _block_full(mp["block"], cfg, hin, pos[:, None], kind="full",
                             mesh=mesh)
    with jax.named_scope(scopes.HEAD):
        return _head(params, cfg, hout)[:, 0], hout[:, 0]


def _spec_zero_rejected(cfg: ModelConfig, cache, pos, a, *, k: int,
                        block_tables=None):
    """Scrub the KV written for rejected draft positions.

    The verify chunk writes all ``k+1`` positions before acceptance is
    known; per slot, positions ``pos + a .. pos + k`` hold rejected
    drafts (``a`` = accepted length; done rows pass a=0 so every write
    is scrubbed).  Contiguous caches zero them in place — bit-identical
    to the never-written state token-by-token decode leaves behind —
    with KEPT positions diverted out of bounds (scatters drop OOB).
    Paged caches zero through the block tables with kept positions
    diverted to the trash block row 0 (table gathers clamp, and table
    columns past the allocation already point at trash).
    """
    B = pos.shape[0]
    jj = jnp.arange(k + 1)
    rej = jj[None, :] >= a[:, None]                      # (B, k+1)
    tgt = pos[:, None] + jj[None, :]                     # (B, k+1)
    pol = quant.policy_of(cache)
    bat = decode_cache_batch_axes(cfg, policy=pol)
    seq = decode_cache_seq_axes(cfg, policy=pol)
    bidx = jnp.arange(B)[:, None]

    def zero_leaf(leaf, bax, sax):
        if sax < 0:
            return leaf
        sax2 = sax if sax > bax else sax + 1
        l = jnp.moveaxis(jnp.moveaxis(leaf, bax, 0), sax2, 1)
        if block_tables is None:
            p = jnp.where(rej, tgt, l.shape[1])          # kept -> OOB drop
            l = l.at[bidx, p].set(0)
        else:
            bl = l.shape[1]
            blk = block_tables[bidx, tgt // bl]
            blk = jnp.where(rej, blk, 0)                 # kept -> trash row
            l = l.at[blk, tgt % bl].set(0)
        return jnp.moveaxis(jnp.moveaxis(l, 1, sax2), 0, bax)

    return jax.tree.map(zero_leaf, cache, bat, seq)


def _scan_generate(params, cfg: ModelConfig, cache, tok, pos, rem, done,
                   keys, eos, *, steps, sampler, return_logits, mesh,
                   block_tables=None):
    """The scanned decode body shared by the contiguous and paged paths."""

    def body(carry, _):
        tok, pos, rem, done, keys, cache = carry
        live = ~done
        logits, cache = decode_step(params, cfg, cache, tok[:, None], pos,
                                    mesh=mesh, block_tables=block_tables,
                                    live=live)
        ks = jax.vmap(lambda k: jax.random.split(k, 2))(keys)
        sampled = sampler(ks[:, 0], logits)
        rem2 = rem - live.astype(rem.dtype)
        done2 = done | (live & ((sampled == eos) | (rem2 <= 0)))
        tok2 = jnp.where(live, sampled, tok)
        # finished slots stop advancing: their (stale) writes pin to
        # one in-capacity position until the slot is re-admitted
        pos2 = jnp.where(live, pos + 1, pos)
        out = (sampled, live, logits) if return_logits else (sampled, live)
        return (tok2, pos2, rem2, done2, ks[:, 1], cache), out

    carry, ys = jax.lax.scan(body, (tok, pos, rem, done, keys, cache),
                             None, length=steps)
    tok, pos, rem, done, keys, cache = carry
    res = {"tokens": ys[0].T, "valid": ys[1].T, "next_tok": tok,
           "pos": pos, "remaining": rem, "done": done, "rng": keys,
           "cache": cache}
    if return_logits:
        res["logits"] = jnp.moveaxis(ys[2], 0, 1)
    return res


def _scan_generate_spec(params, cfg: ModelConfig, cache, tok, pos, rem, done,
                        keys, h, eos, *, steps, k, sampler, mesh,
                        block_tables=None):
    """Self-speculative scanned decode: each step drafts ``k`` tokens
    with the model's own MTP head, verifies all ``k+1`` positions in ONE
    C=(k+1) pass through the shared ``_chunk_hidden`` decode body, and
    advances each slot by its accepted length (>= 1 emission per live
    step, <= k+1).

    Greedy acceptance is an exact argmax-prefix match, so the emitted
    stream is bit-identical to token-by-token decode; stochastic
    samplers use residual rejection sampling (``sampler.verify``) whose
    emitted marginal equals the target distribution.  The carry gains
    ``h`` (B, D): the final-normed hidden of the position that emitted
    the pending token, seeding the next step's draft chain.  Rejected
    draft writes are scrubbed after acceptance so slot cache state
    matches token-by-token decode exactly.
    """
    verify = _verify_for(sampler)
    B = tok.shape[0]
    C = k + 1

    def body(carry, _):
        tok, pos, rem, done, keys, h, cache = carry
        live = ~done
        ks = jax.vmap(lambda kk: jax.random.split(kk, C + 1))(keys)

        # ---- draft: chain the depth-1 MTP head greedily, k times ----
        drafts = []
        dh, dt = h, tok
        for j in range(k):
            dlogits, dh = _mtp_draft(params, cfg, dh, dt,
                                     jnp.maximum(pos - 1 + j, 0), mesh=mesh)
            dt = jnp.argmax(dlogits, -1).astype(jnp.int32)
            drafts.append(dt)

        # ---- verify: one C=k+1 forward through the decode body ----
        chunk = jnp.stack([tok] + drafts, axis=1)         # (B, C)
        cpos = pos[:, None] + jnp.arange(C)[None, :]
        x = _embed(params, cfg, chunk)
        lv = jnp.broadcast_to(live[:, None], (B, C))
        hc, cache = _chunk_hidden(params, cfg, cache, x, cpos, mesh=mesh,
                                  block_tables=block_tables, live=lv)
        with jax.named_scope(scopes.HEAD):
            logits = _head(params, cfg, hc)               # (B, C, V)

        # ---- accept: emission chain with in-chunk eos/budget stops ----
        # position j's logits verify draft j+1 (j < k) or sample the
        # bonus token (j = k); a rejection emits the verifier's token
        # and ends the chain, so every live step emits at least once.
        emit = live
        toks_out, valid_out = [], []
        a = jnp.zeros((B,), jnp.int32)
        new_tok, new_done = tok, done
        for j in range(C):
            if j < k:
                tj, acc = verify(ks[:, j], logits[:, j], drafts[j])
            else:
                tj = sampler(ks[:, j], logits[:, j])
                acc = jnp.zeros((B,), bool)
            valid = emit
            rem = rem - valid.astype(rem.dtype)
            stop = valid & ((tj == eos) | (rem <= 0))
            new_done = new_done | stop
            new_tok = jnp.where(valid, tj, new_tok)
            a = a + valid.astype(jnp.int32)
            toks_out.append(tj)
            valid_out.append(valid)
            emit = emit & acc & ~stop
        new_pos = jnp.where(live, pos + a, pos)
        idx = jnp.clip(a - 1, 0, k)
        new_h = jnp.take_along_axis(hc, idx[:, None, None], axis=1)[:, 0]
        new_h = jnp.where(live[:, None], new_h, h)
        # scrub everything past the accepted frontier.  Dead lanes
        # (a = 0) keep chunk position 0: the plain scan re-writes the
        # pending token's kv at the parked frontier every step, and
        # position 0 of the verify chunk is that exact write, so keeping
        # it preserves bit-identity of the whole cache
        cache = _spec_zero_rejected(cfg, cache, pos, jnp.maximum(a, 1), k=k,
                                    block_tables=block_tables)
        out = (jnp.stack(toks_out, 1), jnp.stack(valid_out, 1))
        return (new_tok, new_pos, rem, new_done, ks[:, C], new_h, cache), out

    carry, ys = jax.lax.scan(body, (tok, pos, rem, done, keys, h, cache),
                             None, length=steps)
    tok, pos, rem, done, keys, h, cache = carry
    return {"tokens": jnp.moveaxis(ys[0], 0, 1).reshape(B, steps * C),
            "valid": jnp.moveaxis(ys[1], 0, 1).reshape(B, steps * C),
            "next_tok": tok, "pos": pos, "remaining": rem, "done": done,
            "rng": keys, "h_spec": h, "cache": cache}


@functools.lru_cache(maxsize=32)
def _generate_spec_fn(cfg: ModelConfig, steps: int, k: int, sampler, mesh):
    """Compiled speculative scanned-decode body, cached per
    (cfg, steps, k, sampler, mesh).  The cache operand is donated."""

    def run(params, cache, tok, pos, rem, done, keys, h, eos):
        return _scan_generate_spec(params, cfg, cache, tok, pos, rem, done,
                                   keys, h, eos, steps=steps, k=k,
                                   sampler=sampler, mesh=mesh)

    return jax.jit(run, donate_argnums=(1,))


@functools.lru_cache(maxsize=32)
def _generate_spec_paged_fn(cfg: ModelConfig, steps: int, k: int, sampler,
                            mesh):
    """Paged twin of ``_generate_spec_fn``: block tables threaded into
    every verify chunk (reads, writes, and the rejected-KV scrub)."""

    def run(params, cache, bt, tok, pos, rem, done, keys, h, eos):
        return _scan_generate_spec(params, cfg, cache, tok, pos, rem, done,
                                   keys, h, eos, steps=steps, k=k,
                                   sampler=sampler, mesh=mesh,
                                   block_tables=bt)

    return jax.jit(run, donate_argnums=(1,))


@functools.lru_cache(maxsize=32)
def _generate_fn(cfg: ModelConfig, steps: int, sampler, return_logits: bool,
                 mesh):
    """Compiled scanned-decode body, cached per (cfg, steps, sampler).

    ``sampler`` must be hashable (module-level function or frozen
    dataclass instance, see repro/serve/sampling.py).  The cache operand
    is donated: one host dispatch runs ``steps`` decode steps.
    """

    def run(params, cache, tok, pos, rem, done, keys, eos):
        return _scan_generate(params, cfg, cache, tok, pos, rem, done, keys,
                              eos, steps=steps, sampler=sampler,
                              return_logits=return_logits, mesh=mesh)

    return jax.jit(run, donate_argnums=(1,))


@functools.lru_cache(maxsize=32)
def _generate_paged_fn(cfg: ModelConfig, steps: int, sampler,
                       return_logits: bool, mesh):
    """Paged twin of ``_generate_fn``: same scan, plus the (read-only)
    per-slot block tables threaded into every ``decode_step``."""

    def run(params, cache, bt, tok, pos, rem, done, keys, eos):
        return _scan_generate(params, cfg, cache, tok, pos, rem, done, keys,
                              eos, steps=steps, sampler=sampler,
                              return_logits=return_logits, mesh=mesh,
                              block_tables=bt)

    return jax.jit(run, donate_argnums=(1,))


def generate(params, cfg: ModelConfig, cache, first_tok, pos0, *, steps: int,
             sampler=None, rng=None, eos_id=None, remaining=None, mesh=None,
             return_logits: bool = False, block_tables=None,
             speculate: int = 0, spec_h=None):
    """Run ``steps`` decode steps as ONE ``lax.scan`` dispatch.

    ``first_tok`` (B,) or (B, 1) is the token fed at ``pos0`` (B,) —
    normally the sampler applied to the prefill logits, so it is already
    emission #1 of the request; the scan emits ``steps`` more.  The
    decode cache is donated to the compiled scan.

    Per-slot engine state rides through the scan carry: ``remaining``
    (emissions still allowed; slots with 0 start done and only produce
    discarded garbage), ``eos_id`` stopping, and per-slot RNG ``rng``
    (B, 2) split once per step regardless of slot liveness, so a scan
    split into segments samples identically to one long scan.

    With ``block_tables`` (B, nbt) the cache is the block-paged layout of
    ``init_paged_cache`` and every decode step reads/writes through the
    tables; the tables themselves are fixed for the whole segment (the
    engine allocates a request's blocks at admission).

    With ``speculate=k`` (> 0) each scan step drafts ``k`` tokens via
    the MTP head and verifies ``k+1`` positions in one C=(k+1) chunk —
    per-slot advance becomes the accepted length, ``tokens``/``valid``
    widen to (B, steps * (k+1)), the result gains the carried ``h_spec``
    (pass it back as ``spec_h`` to continue a segmented decode;
    admission starts from zeros — a cold first draft just gets
    rejected), and the RNG stream differs from non-speculative decode
    (k+2 splits per step).  Requires an MTP head (``cfg.n_mtp`` with
    ``params["mtp"]`` — dense/moe/vlm families).

    Returns a dict with ``tokens``/``valid`` (B, steps), the carried
    ``next_tok``/``pos``/``remaining``/``done``/``rng``, the updated
    ``cache``, and (when ``return_logits``) the raw per-step ``logits``
    (B, steps, V) — bit-identical to a per-token ``decode_step`` loop.
    """
    if sampler is None:
        sampler = greedy_sample
    B = first_tok.shape[0]
    tok = jnp.asarray(first_tok).reshape(B).astype(jnp.int32)
    pos0 = jnp.asarray(pos0).reshape(B).astype(jnp.int32)
    if rng is None:
        rng = jax.random.split(jax.random.PRNGKey(0), B)
    if remaining is None:
        remaining = jnp.full((B,), steps, jnp.int32)
    remaining = jnp.asarray(remaining).reshape(B).astype(jnp.int32)
    eos = jnp.int32(-1 if eos_id is None else eos_id)
    if speculate:
        if return_logits:
            raise ValueError("return_logits is not supported with "
                             "speculative decode")
        if not (cfg.n_mtp and "mtp" in params):
            raise ValueError(
                "speculative decode needs an MTP head (cfg.n_mtp > 0 with "
                "params['mtp'] — dense/moe/vlm families only)")
        h = (jnp.zeros((B, cfg.d_model), _dtype(cfg)) if spec_h is None
             else jnp.asarray(spec_h, _dtype(cfg)).reshape(B, cfg.d_model))
        if block_tables is not None:
            fn = _generate_spec_paged_fn(cfg, int(steps), int(speculate),
                                         sampler, mesh)
            return fn(params, cache, jnp.asarray(block_tables, jnp.int32),
                      tok, pos0, remaining, remaining <= 0, rng, h, eos)
        fn = _generate_spec_fn(cfg, int(steps), int(speculate), sampler, mesh)
        return fn(params, cache, tok, pos0, remaining, remaining <= 0, rng,
                  h, eos)
    if block_tables is not None:
        fn = _generate_paged_fn(cfg, int(steps), sampler, bool(return_logits),
                                mesh)
        return fn(params, cache, jnp.asarray(block_tables, jnp.int32), tok,
                  pos0, remaining, remaining <= 0, rng, eos)
    fn = _generate_fn(cfg, int(steps), sampler, bool(return_logits), mesh)
    return fn(params, cache, tok, pos0, remaining, remaining <= 0, rng, eos)
