"""Parameter / optimizer-state sharding rules.

The rules map parameter *paths* (and ranks) to PartitionSpecs over the
production mesh axes ("pod", "data", "model"):

* Megatron-style tensor parallelism on the "model" axis — attention heads
  and FFN hidden columns; expert-parallel MoE weights (leading expert dim
  on "model", matching the shard_map all-to-all dispatch).
* FSDP/ZeRO-style weight + optimizer sharding over the "data" axis — the
  first large replicated dim of each leaf is additionally sharded over
  "data" (and "pod" when present).  This is what keeps 671B-class configs
  within a v5e's HBM (see EXPERIMENTS.md §Dry-run).

Stacked layer params (leading scan "group" axes) are handled generically:
rules match the *trailing* dims, leading axes are padded with None.
"""
from __future__ import annotations

import re
from typing import Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.utils.pytree import path_str


# (path regex, trailing-dims spec) — first match wins.
_RULES: Tuple[Tuple[str, Tuple], ...] = (
    (r"(^|/)embed$",                     (None, "model")),
    (r"(^|/)lm_head$",                   (None, "model")),
    # attention
    (r"(x?attn)/w[qkv]$",                (None, "model")),
    (r"(x?attn)/wo$",                    ("model", None)),
    # MLA
    (r"wq_a$",                           (None, None)),
    (r"wq_b$",                           (None, "model")),
    (r"wkv_a$",                          (None, None)),
    (r"w[kv]_b$",                        ("model", None, None)),
    # MoE (expert-parallel: expert dim on "model")
    (r"moe/router$",                     (None, None)),
    (r"moe/wi_gate$|moe/wi_up$|moe/wo$", ("model", None, None)),
    # dense MLPs (incl. shared experts)
    (r"wi_gate$|wi_up$|wi$",             (None, "model")),
    (r"(mlp|shared)/wo$",                ("model", None)),
    # SSM
    (r"in_proj$",                        (None, "model")),
    (r"out_proj$",                       ("model", None)),
    (r"conv_w$",                         (None, "model")),
    (r"conv_b$",                         ("model",)),
    (r"A_log$|/D$|dt_bias$",             (None,)),
    # MTP glue
    (r"mtp/proj$",                       (None, None)),
)


def data_axes_of(mesh: Mesh):
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _trailing_spec(path: str, leaf) -> Tuple:
    for pat, spec in _RULES:
        if re.search(pat, path):
            return spec
    return (None,) * leaf.ndim  # norms, scalars, biases: replicate


def _full_spec(path: str, leaf, mesh: Mesh, *, fsdp: bool,
               ep_all: bool = False) -> P:
    trailing = _trailing_spec(path, leaf)
    trailing = trailing[-leaf.ndim:] if leaf.ndim else ()
    spec = [None] * (leaf.ndim - len(trailing)) + list(trailing)
    # serving layout: shard the expert dim over the WHOLE mesh so expert
    # weights never move at decode time (1 expert per device on 16x16)
    if ep_all and re.search(r"moe/(wi_gate|wi_up|wo)$", path):
        all_axes = tuple(mesh.axis_names)
        n_all = mesh.size
        e_dim = leaf.ndim - 3
        if leaf.shape[e_dim] % n_all == 0:
            spec = [None] * leaf.ndim
            spec[e_dim] = all_axes
            return P(*spec)
    # pjit in_shardings require exact divisibility: drop non-dividing
    # assignments and re-place "model" on another dim when possible
    # (e.g. Qwen's 60 experts on a 16-way axis -> shard d_ff instead).
    model = mesh.shape.get("model", 1)
    dropped_model = False
    for i, s in enumerate(spec):
        if s == "model" and leaf.shape[i] % model != 0:
            spec[i] = None
            dropped_model = True
    if dropped_model:
        for i in reversed(range(leaf.ndim)):
            if spec[i] is None and leaf.shape[i] % model == 0 \
               and leaf.shape[i] >= model:
                spec[i] = "model"
                break
    if fsdp and leaf.ndim >= 2:
        daxes = data_axes_of(mesh)
        n_data = 1
        for a in daxes:
            n_data *= mesh.shape[a]
        if n_data > 1:
            for i, s in enumerate(spec):
                if s is None and leaf.shape[i] % n_data == 0 and leaf.shape[i] >= n_data:
                    spec[i] = daxes if len(daxes) > 1 else daxes[0]
                    break
    return P(*spec)


def param_specs(params, mesh: Mesh, *, fsdp: bool = True,
                ep_all: bool = False):
    """PartitionSpec pytree matching ``params``.

    ``ep_all``: serving layout — MoE expert dims shard over every mesh
    axis (used with the ``replicated_ep`` decode path)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    specs = [_full_spec(path_str(p), leaf, mesh, fsdp=fsdp, ep_all=ep_all)
             for p, leaf in flat]
    return jax.tree_util.tree_unflatten(treedef, specs)


def opt_state_specs(params, mesh: Mesh, *, fsdp: bool = True,
                    state=None):
    """Specs for AdamW state {m, v, step}: moments follow the params.

    Pass the actual ``state`` to cover quantized moment policies — an
    int8-v state carries a ``"v_scale"`` tree of scalar per-tensor
    scales, which replicate."""
    ps = param_specs(params, mesh, fsdp=fsdp)
    specs = {"m": ps, "v": ps, "step": P()}
    if state is not None and "v_scale" in state:
        specs["v_scale"] = jax.tree.map(lambda _: P(), state["v_scale"])
    return specs


def fleet_specs(tree, mesh: Mesh):
    """Stacked-fleet layout over a ``("hosts",)`` mesh.

    The fleet drivers stack per-device params / optimizer state / batch
    streams along a leading device axis (``federated.device.train_fleet``);
    that axis shards over "hosts" when divisible — each host owns a
    contiguous run of simulated devices — and everything else (per-lane
    scalars that stacked into non-divisible vectors, e.g. a padded
    remainder) replicates.  Non-divisible dims replicate, never error.
    """
    n = mesh.shape["hosts"]

    def spec(leaf):
        nd = getattr(leaf, "ndim", 0)
        if nd >= 1 and n > 1 and leaf.shape[0] % n == 0:
            return P(*(["hosts"] + [None] * (nd - 1)))
        return P(*([None] * nd))

    return jax.tree.map(spec, tree)


def host_resident_bytes(tree, device_index: int = 0) -> int:
    """Bytes of ``tree`` resident on ONE device of the fleet mesh.

    For a ``fleet_specs``-sharded state this is ``total / n_hosts`` plus
    any replicated leaves — the per-host footprint that bounds how many
    simulated devices a host can keep resident between rounds."""
    total = 0
    for leaf in jax.tree.leaves(tree):
        for sh in leaf.addressable_shards:
            if sh.device.id == device_index:
                total += int(sh.data.size) * sh.data.dtype.itemsize
    return total


def batch_spec(batch, mesh: Mesh):
    """Shard every batch array's leading (batch) dim over the data axes."""
    daxes = data_axes_of(mesh)
    ax = daxes if len(daxes) > 1 else (daxes[0] if daxes else None)
    n_data = 1
    for a in daxes:
        n_data *= mesh.shape[a]

    def spec(x):
        if x.ndim == 0 or x.shape[0] % n_data != 0:
            return P(*([None] * x.ndim))  # tiny decode batches replicate
        return P(*([ax] + [None] * (x.ndim - 1)))

    return jax.tree.map(spec, batch)


def named(mesh: Mesh, spec_tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda s: isinstance(s, P))


def cache_specs(cache, mesh: Mesh, *, batch: int, seq: int):
    """Decode-cache sharding.

    Heuristic per leaf: shard the batch-sized dim over the data axes when
    divisible; then shard the cache-sequence dim over "model" (or over
    *all* axes when the batch is too small to shard — the long_500k
    sequence-parallel decode layout).  Head-sized dims stay replicated
    (they are often non-divisible GQA KV head counts; XLA pads).
    """
    daxes = data_axes_of(mesh)
    n_data = 1
    for a in daxes:
        n_data *= mesh.shape[a]
    model = mesh.shape.get("model", 1)
    dax = daxes if len(daxes) > 1 else (daxes[0] if daxes else None)
    all_axes = tuple(list(daxes) + ["model"])

    def spec(leaf):
        s = [None] * leaf.ndim
        batch_done = False
        for i, d in enumerate(leaf.shape):
            if d == batch and batch % n_data == 0 and n_data > 1:
                s[i] = dax
                batch_done = True
                break
        for i, d in enumerate(leaf.shape):
            if s[i] is None and d == seq and seq > 1:
                if batch_done and d % model == 0:
                    s[i] = "model"
                elif not batch_done and d % (n_data * model) == 0:
                    s[i] = all_axes
                break
        return P(*s)

    return jax.tree.map(spec, cache)


def paged_cache_specs(cache, mesh: Mesh, *, batch_axes, seq_axes):
    """Paged-cache sharding: block pools + slot-resident leaves.

    ``batch_axes`` / ``seq_axes`` are the per-leaf axis trees from
    ``models.model.decode_cache_batch_axes`` / ``decode_cache_seq_axes``
    (the paged layout keeps the contiguous layout's axis positions: the
    batch axis holds ``n_blocks`` for pool leaves, ``n_slots`` for
    slot-resident ones).

    Pool leaves (seq axis >= 0): the ``n_blocks`` dim shards over the
    data axes — each device owns a CONTIGUOUS run of block ids, which is
    exactly the split ``serve.paged.PagedAllocator``'s per-shard free
    lists track — and the trailing feature dim shards over "model" when
    divisible (KV heads x head_dim, MLA latent width).  Slot-resident
    leaves (seq axis < 0: ssm/hybrid state, encdec cross KV + memory)
    shard their ``n_slots`` dim over the data axes like the contiguous
    cache.  Non-divisible dims replicate — never an error.
    """
    daxes = data_axes_of(mesh)
    n_data = 1
    for a in daxes:
        n_data *= mesh.shape[a]
    model = mesh.shape.get("model", 1)
    dax = daxes if len(daxes) > 1 else (daxes[0] if daxes else None)

    def spec(leaf, bax, sax):
        s = [None] * leaf.ndim
        if n_data > 1 and leaf.shape[bax] % n_data == 0:
            s[bax] = dax
        if sax >= 0 and model > 1:
            last = leaf.ndim - 1
            if last != bax and s[last] is None \
               and leaf.shape[last] % model == 0 and leaf.shape[last] >= model:
                s[last] = "model"
        return P(*s)

    return jax.tree.map(spec, cache, batch_axes, seq_axes)
