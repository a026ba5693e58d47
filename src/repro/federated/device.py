"""Edge-device simulation: local on-device LLM training (paper §IV.A).

Each device independently picks an on-device LLM family suited to its
hardware (paper: GPT-2, GPT-2-Medium, TinyLlama, OLMo-1.2B, BLOOM-1.1B),
trains it on private local data to convergence, and uploads it **once**
(one-shot FL, Eq. 5) together with a low-rank data embedding for
clustering.

The fleet is simulated in-process.  Two compiled hot paths (see
docs/loops.md):

* ``train_device`` runs the whole local epoch as ONE ``lax.scan``-ed
  XLA program over pre-generated stacked batches — a single host sync
  per epoch instead of one per step;
* ``train_fleet`` buckets devices by ``ModelConfig`` and ``jax.vmap``s
  the scanned epoch over the device axis, so N same-arch devices train
  as one compiled program instead of N sequential loops.

Communication cost accounting uses the *configured* model's true
parameter count (so Fig. 8-style numbers reflect the paper's device
models even when the simulated training runs reduced CPU variants).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.federated import FederatedCorpus
from repro.models import model as M
from repro.models.config import ModelConfig
from repro.optim import adamw_init, adamw_update, cosine_schedule, scan_epoch
from repro.utils.pytree import tree_bytes


@dataclasses.dataclass(frozen=True)
class TrafficModel:
    """Seeded per-round traffic behaviour of one simulated edge device.

    Report latency is lognormal (``median_latency_s`` scaled by
    ``exp(sigma * N(0,1))`` — the long straggler tail real fleets show),
    each round the device is offline with probability ``dropout_p``, and
    ``avail_period``/``avail_duty`` model a battery / charging window:
    the device is only reachable during the first ``avail_duty`` rounds
    of every ``avail_period`` (0 = always available).  All draws are
    pure functions of ``(seed, device_id, round)`` — see
    ``sample_traffic`` — so fleet simulations replay bit-identically and
    a device's behaviour never depends on what the rest of the fleet did.
    """
    median_latency_s: float = 1.0
    latency_sigma: float = 0.5
    dropout_p: float = 0.0
    avail_period: int = 0
    avail_duty: int = 0


# named presets for --straggler-profile
STRAGGLER_PROFILES = {
    "none": TrafficModel(),
    "mild": TrafficModel(median_latency_s=1.0, latency_sigma=0.5,
                         dropout_p=0.1),
    "harsh": TrafficModel(median_latency_s=1.5, latency_sigma=1.0,
                          dropout_p=0.3, avail_period=8, avail_duty=6),
}


@dataclasses.dataclass
class DeviceSpec:
    device_id: int
    cfg: ModelConfig            # the on-device LLM this device runs
    arch_id: int                # index into the device-model family list
    domain_id: int              # ground-truth knowledge domain (hidden)
    # full-size variant of ``cfg`` when the simulation trains a reduced
    # CPU stand-in; comm-cost accounting (Fig. 8) bills this one.
    full_cfg: Optional[ModelConfig] = None
    # straggler/dropout behaviour for async rounds (None = ideal link)
    traffic: Optional[TrafficModel] = None

    @property
    def comm_cfg(self) -> ModelConfig:
        return self.full_cfg or self.cfg


def sample_traffic(spec: DeviceSpec, round_idx: int, seed: int):
    """Deterministic ``(latency_s, online)`` draw for (device, round).

    Keyed on ``(seed, device_id, round)`` only — independent of fleet
    history, so a device that dropped out rejoins with the identical
    latency/dropout stream it would always have had."""
    tm = spec.traffic or TrafficModel()
    if tm.avail_period and (round_idx % tm.avail_period) >= tm.avail_duty:
        return 0.0, False
    rng = np.random.default_rng(
        (seed, 7_700_000 + spec.device_id, round_idx))
    dropped = bool(rng.random() < tm.dropout_p)
    latency = float(tm.median_latency_s * np.exp(tm.latency_sigma *
                                                 rng.standard_normal()))
    return latency, not dropped


@functools.lru_cache(maxsize=64)
def model_param_bytes(cfg: ModelConfig) -> int:
    """Weight bytes of ``cfg`` at its configured dtype, from abstract
    shapes only (no allocation — works for 100B+ configs)."""
    shapes = jax.eval_shape(lambda: M.init_params(jax.random.PRNGKey(0), cfg))
    return tree_bytes(shapes)


def device_upload_bytes(cfg: ModelConfig, embedding_dim: int = 32) -> int:
    """One-shot upload = model weights + the tiny data embedding (Eq. 5).

    Billed from the configured ``ModelConfig``'s true parameter count,
    NOT from whatever reduced variant the simulation happens to train.
    """
    return model_param_bytes(cfg) + embedding_dim * 4


# ---------------------------------------------------------------------------
# compiled local-training epochs
# ---------------------------------------------------------------------------

def _step_core(cfg: ModelConfig) -> Callable:
    """The one local-training step: shared by the per-step reference
    loop and the scanned epoch, so the two paths cannot diverge."""

    def step(params, opt, b, lr_now):
        (loss, _), g = jax.value_and_grad(
            lambda p: M.loss_fn(p, cfg, b), has_aux=True)(params)
        params, opt, _ = adamw_update(g, opt, params, lr=lr_now)
        return params, opt, loss

    return step


def _epoch_core(cfg: ModelConfig, steps: int, lr: float, warmup: int,
                total_steps: Optional[int] = None) -> Callable:
    """Un-jitted scanned epoch: (params, opt, stacked batches[, start])
    -> (params, opt, per-step losses).  The lr schedule is evaluated
    inside the scan from the step counter.

    ``total_steps`` sets the schedule horizon when this epoch is one
    *round* of a longer run (async fleet rounds); ``start`` then offsets
    the counter, so round ``r`` of ``k`` steps computes exactly steps
    ``[r*k, (r+1)*k)`` of the equivalent single-scan epoch."""
    sched = cosine_schedule(lr, total_steps or steps, warmup=warmup)
    step = _step_core(cfg)

    def carry_step(carry, b, lr_now):
        params, opt, loss = step(*carry, b, lr_now)
        return (params, opt), loss

    scanned = scan_epoch(carry_step, sched, steps)

    def epoch(params, opt, batches, start=0):
        (params, opt), losses = scanned((params, opt), batches, start)
        return params, opt, losses

    return epoch


@functools.lru_cache(maxsize=64)
def _device_epoch_fn(cfg: ModelConfig, steps: int, lr: float, warmup: int):
    return jax.jit(_epoch_core(cfg, steps, lr, warmup),
                   donate_argnums=(0, 1))


@functools.lru_cache(maxsize=64)
def _fleet_epoch_fn(cfg: ModelConfig, steps: int, lr: float, warmup: int):
    """The scanned epoch vmapped over a leading device axis — one
    compiled program trains every same-arch device in the bucket."""
    return jax.jit(jax.vmap(
        lambda p, o, b: _epoch_core(cfg, steps, lr, warmup)(p, o, b)),
        donate_argnums=(0, 1))


@functools.lru_cache(maxsize=64)
def _fleet_round_fn(cfg: ModelConfig, steps: int, lr: float, warmup: int,
                    total_steps: int):
    """One async *round* for a whole arch bucket: the scanned epoch
    vmapped over devices, with per-device schedule offsets (``start``,
    each device's local step into the ``total_steps`` horizon) and an
    ``active`` mask — offline devices' params/opt pass through untouched
    and their loss lanes come back NaN, so the round compiles ONCE per
    bucket shape regardless of which subset of devices is online."""
    epoch = _epoch_core(cfg, steps, lr, warmup, total_steps=total_steps)

    def device_round(params, opt, batches, start, active):
        p2, o2, losses = epoch(params, opt, batches, start)
        sel = lambda new, old: jnp.where(active, new, old)
        return (jax.tree.map(sel, p2, params), jax.tree.map(sel, o2, opt),
                jnp.where(active, losses, jnp.nan))

    return jax.jit(jax.vmap(device_round), donate_argnums=(0, 1))


@functools.lru_cache(maxsize=64)
def _device_step_fn(cfg: ModelConfig):
    """Per-step reference path (kept for equivalence tests and the
    fleet-scaling benchmark baseline)."""
    return jax.jit(_step_core(cfg))


def _device_params(spec: DeviceSpec, seed: int):
    return M.init_params(
        jax.random.PRNGKey(seed * 100003 + spec.device_id), spec.cfg)


def _device_init(spec: DeviceSpec, seed: int, state_policy: str = ""):
    params = _device_params(spec, seed)
    return params, adamw_init(params, policy=state_policy)


def _upload(spec: DeviceSpec, corpus: FederatedCorpus, params,
            losses) -> Dict:
    return {
        # an upload goes to the server, which keeps it in host memory:
        # the accelerator's memory is for the training that follows
        "params": jax.device_get(params),
        "embedding": corpus.device_embedding(spec.device_id),
        "losses": [float(x) for x in np.asarray(losses)],
        "upload_bytes": device_upload_bytes(spec.comm_cfg),
        "arch_id": spec.arch_id,
        "device_id": spec.device_id,
    }


def train_device(spec: DeviceSpec, corpus: FederatedCorpus, *, steps: int,
                 batch: int, seq_len: int, lr: float = 3e-3,
                 seed: int = 0, compiled: bool = True,
                 state_policy: str = "") -> Dict:
    """Local training.  Returns {"params", "embedding", "losses", ...}.

    ``compiled=True`` (default) runs the epoch as one scanned program;
    ``compiled=False`` keeps the historical per-step loop (one host sync
    per step) for equivalence tests.

    ``state_policy`` ('' | 'bf16' | 'int8') sets the AdamW moment
    storage (see ``repro.optim.adamw.resolve_moment_policy``); the
    scanned epoch needs no plumbing — it retraces per state structure.
    """
    params, opt = _device_init(spec, seed, state_policy)
    warmup = max(steps // 20, 1)
    if compiled:
        batches = corpus.device_batches(spec.device_id, steps, batch, seq_len)
        epoch = _device_epoch_fn(spec.cfg, steps, lr, warmup)
        params, opt, losses = epoch(params, opt, batches)
        return _upload(spec, corpus, params, losses)

    sched = cosine_schedule(lr, steps, warmup=warmup)
    step_fn = _device_step_fn(spec.cfg)
    losses = []
    for s in range(steps):
        b = corpus.device_batch(spec.device_id, batch, seq_len, step=s)
        params, opt, loss = step_fn(params, opt, b, sched(s))
        losses.append(float(loss))
    return _upload(spec, corpus, params, losses)


def fleet_buckets(fleet: Sequence[DeviceSpec]
                  ) -> Dict[ModelConfig, List[DeviceSpec]]:
    """Group the fleet by (hashable) ``ModelConfig``, preserving order."""
    buckets: Dict[ModelConfig, List[DeviceSpec]] = {}
    for spec in fleet:
        buckets.setdefault(spec.cfg, []).append(spec)
    return buckets


def _stack_trees(trees):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def _init_bucket(specs: Sequence[DeviceSpec], seed: int,
                 state_policy: str = ""):
    """(params, opt) of one arch bucket, stacked along a device axis.

    Equal to stacking each device's ``_device_init``, but the optimizer
    state is built on the stacked params: a per-device copy of the
    bucket's whole training state never sits beside the stacked one."""
    params = _stack_trees([_device_params(s, seed) for s in specs])
    return params, jax.vmap(
        functools.partial(adamw_init, policy=state_policy))(params)


def _pad_lanes(tree, n_pad: int):
    """Append ``n_pad`` copies of lane 0 along the stacked device axis
    (multi-host runs pad each bucket to a multiple of the host count;
    padded lanes are discarded after the round)."""
    if n_pad == 0:
        return tree
    return jax.tree.map(
        lambda x: jnp.concatenate(
            [x, jnp.broadcast_to(x[:1], (n_pad,) + x.shape[1:])]), tree)


def _shard_bucket(mesh, *trees):
    """Lay a bucket's stacked trees out over the ``("hosts",)`` mesh:
    the leading device axis shards over hosts (see
    ``sharding.rules.fleet_specs``), so fleet size scales with hosts —
    each host holds ``n_devices / n_hosts`` device states."""
    from repro.sharding import rules
    return tuple(
        jax.device_put(t, rules.named(mesh, rules.fleet_specs(t, mesh)))
        for t in trees)


def train_fleet(fleet: Sequence[DeviceSpec], corpus: FederatedCorpus, *,
                steps: int, batch: int, seq_len: int, lr: float = 3e-3,
                seed: int = 0, state_policy: str = "",
                n_hosts: int = 1, mesh=None) -> List[Dict]:
    """Arch-bucketed compiled fleet training.

    Groups the fleet by ``ModelConfig``, stacks each bucket's init
    params / optimizer state / pre-generated batch streams along a new
    device axis, and runs the vmapped scanned epoch once per bucket.
    Returns uploads in the fleet's original order, identical to calling
    ``train_device`` per spec (same seeds, same batches).

    ``state_policy`` quantizes each device's stacked AdamW moments
    ('bf16' halves them; 'int8' quarters v) so a host fits measurably
    more devices per bucket at equal bytes — the paper's
    resource-constrained edge fleet at scale.

    ``n_hosts > 1`` (or an explicit ``("hosts",)`` ``mesh``) runs each
    bucket through ``jax.pjit``: the stacked device axis is sharded over
    the mesh (buckets pad to a multiple of the host count with discarded
    lanes), so the per-host resident state — and therefore the fleet
    size one simulation can hold — scales linearly with hosts.  Lanes
    are independent, so the sharded run is bit-identical to ``n_hosts=1``.
    """
    if mesh is None and n_hosts > 1:
        from repro.launch.mesh import make_fleet_mesh
        mesh = make_fleet_mesh(n_hosts)
    n_shards = mesh.shape["hosts"] if mesh is not None else 1

    uploads: Dict[int, Dict] = {}
    warmup = max(steps // 20, 1)
    for cfg, specs in fleet_buckets(fleet).items():
        params, opt = _init_bucket(specs, seed, state_policy)
        batches = _stack_trees(
            [corpus.device_batches(s.device_id, steps, batch, seq_len)
             for s in specs])
        if mesh is not None:
            n_pad = (-len(specs)) % n_shards
            params, opt, batches = (_pad_lanes(t, n_pad)
                                    for t in (params, opt, batches))
            params, opt, batches = _shard_bucket(mesh, params, opt, batches)
        epoch = _fleet_epoch_fn(cfg, steps, lr, warmup)
        params, opt, losses = epoch(params, opt, batches)
        del opt     # free this bucket's optimizer state before the next one
        losses = np.asarray(losses)          # one host sync per bucket
        for i, spec in enumerate(specs):
            uploads[spec.device_id] = _upload(
                spec, corpus, jax.tree.map(lambda x: x[i], params), losses[i])

    return [uploads[spec.device_id] for spec in fleet]
