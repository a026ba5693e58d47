"""DeepFusion central server (paper Fig. 3): the three-phase pipeline.

Phase I   — local knowledge clustering: cluster uploaded on-device LLMs
            by data embeddings into K domains, weight-average per cluster
            into proxy models m̄_i (§IV.B).
Phase II  — cross-architecture KD: distill each proxy into a dense "MoE
            base model" M_i with the VAA module (§IV.C, Eq. 7-11) on
            public server data.
Phase III — merge the K base models into the global MoE (Fig. 6) and
            tune with frozen experts (§IV.D).
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import clustering, distill, merge, proxy, tuning
from repro.core import vaa as vaa_mod
from repro.data.federated import FederatedCorpus
from repro.models import model as M
from repro.models.config import ModelConfig
from repro.optim import adamw_init, adamw_update, cosine_schedule
from repro.utils.pytree import tree_average, tree_size


@dataclasses.dataclass(frozen=True)
class AsyncFleetConfig:
    """Participation schedule for async / hierarchical fleet rounds.

    Per round a sampled subset of the fleet reports its local update;
    the server merges deliverable reports with FedAsync-style
    staleness-discounted weights ``alpha / (1 + staleness)^
    staleness_power`` (``staleness_weight``).  Reports later than
    ``deadline_s`` are handled by ``deadline_policy``:

      * ``"drop"``    — the late update is discarded;
      * ``"stale"``   — it is carried and merged in a later round with
                        its accrued staleness discount;
      * ``"standby"`` — the round over-selects ``over_select`` extra
                        standby devices so the on-time quorum still
                        meets the participation target; late reports
                        are dropped.

    ``hierarchical`` interposes one sub-server per arch bucket: devices
    report edge-locally and only each bucket's merged aggregate crosses
    the global link (comm accounting bills the two tiers separately —
    the merge math is identical to flat mode by construction).
    """
    rounds: int = 3
    steps_per_round: int = 10
    participation: float = 1.0     # fraction of the fleet sampled per round
    alpha: float = 0.6             # FedAsync base mixing weight
    staleness_power: float = 0.5   # a in alpha / (1 + staleness)^a
    deadline_s: float = float("inf")
    deadline_policy: str = "stale"  # "drop" | "stale" | "standby"
    over_select: float = 0.25      # standby headroom (deadline_policy=standby)
    server_momentum: float = 0.0   # G <- mom*G + (1-mom)*round_average
    hierarchical: bool = False     # per-arch-bucket sub-servers (edge tier)
    seed: int = 0

    def validate(self) -> "AsyncFleetConfig":
        if self.deadline_policy not in ("drop", "stale", "standby"):
            raise ValueError(
                f"deadline_policy {self.deadline_policy!r} not in "
                "('drop', 'stale', 'standby')")
        if not (0.0 < self.participation <= 1.0):
            raise ValueError("participation must be in (0, 1]")
        if self.rounds < 1 or self.steps_per_round < 1:
            raise ValueError("rounds and steps_per_round must be >= 1")
        return self


def staleness_weight(alpha: float, staleness: float, power: float) -> float:
    """FedAsync mixing weight for a report ``staleness`` rounds old."""
    return float(alpha) / (1.0 + float(staleness)) ** float(power)


class FleetAggregator:
    """Staleness-discounted per-arch-bucket merging (FedAsync-style).

    Each round's deliverable reports for a bucket are combined into a
    weighted average (weights ``staleness_weight(alpha, tau, power)``)
    and mixed into the bucket's running aggregate under
    ``server_momentum``.  All-fresh reports get equal weights, which is
    computed as the *plain* ``tree_average`` — so with full on-time
    participation one round reproduces the synchronous FedAvg merge
    bit-for-bit (tests/test_fleet_async.py property tests).
    """

    def __init__(self, acfg: AsyncFleetConfig):
        self.acfg = acfg
        self.aggregates: Dict = {}       # bucket key -> merged params
        self.merged_staleness: List[int] = []

    def merge_round(self, bucket_key, reports: Sequence[Dict]):
        """``reports``: [{"device_id", "params", "staleness"}] — merged
        in device-id order so float accumulation is deterministic."""
        if not reports:
            return self.aggregates.get(bucket_key)
        reports = sorted(reports, key=lambda r: r["device_id"])
        ws = [staleness_weight(self.acfg.alpha, r["staleness"],
                               self.acfg.staleness_power) for r in reports]
        self.merged_staleness.extend(int(r["staleness"]) for r in reports)
        if len(set(ws)) == 1:
            # uniform weights ARE the plain average — short-circuiting
            # keeps the all-fresh round bitwise equal to FedAvg
            avg = tree_average([r["params"] for r in reports])
        else:
            total = sum(ws)
            wn = [w / total for w in ws]
            avg = jax.tree.map(
                lambda *xs: sum(w * x.astype(jnp.float32)
                                for w, x in zip(wn, xs)).astype(xs[0].dtype),
                *[r["params"] for r in reports])
        prev = self.aggregates.get(bucket_key)
        mom = self.acfg.server_momentum
        if prev is not None and mom > 0.0:
            avg = jax.tree.map(
                lambda g, a: (mom * g.astype(jnp.float32) +
                              (1.0 - mom) * a.astype(jnp.float32)
                              ).astype(a.dtype), prev, avg)
        self.aggregates[bucket_key] = avg
        return avg

    def staleness_histogram(self) -> Dict[int, int]:
        hist: Dict[int, int] = {}
        for t in self.merged_staleness:
            hist[t] = hist.get(t, 0) + 1
        return hist


@dataclasses.dataclass
class ServerConfig:
    moe_cfg: ModelConfig
    distill_steps: int = 60
    distill_batch: int = 8
    distill_lr: float = 1e-3
    tune_steps: int = 60
    tune_batch: int = 8
    tune_lr: float = 5e-4
    seq_len: int = 64
    alpha: float = 1.0            # L_FM weight (Eq. 11)
    beta: float = 1.0             # L_KL weight (Eq. 11)
    temperature: float = 2.0
    n_stages: int = 4             # J representation stages
    vaa_dim: int = 128
    vaa_heads: int = 4
    p_q: int = 64                 # total VAA queries
    seed: int = 0
    # AdamW moment storage for Phase II distillation ('' | 'bf16' |
    # 'int8', see repro.optim.adamw.resolve_moment_policy); the compiled
    # epoch retraces per state structure, no key change needed
    state_policy: str = ""
    # async fleet participation schedule; None keeps the synchronous
    # one-shot `train_fleet` path (see AsyncFleetConfig)
    schedule: Optional[AsyncFleetConfig] = None


@functools.lru_cache(maxsize=64)
def _distill_epoch_fn(base_cfg, t_cfg, alpha, beta, temperature, n_stages,
                      vaa_heads, p_q, steps, lr, warmup, mesh):
    """One compiled scan-epoch per (student, teacher, hparams) combo —
    proxies sharing a teacher family, and baseline re-runs (FedKMT/OFA),
    reuse it instead of re-jitting.  Trainable/opt buffers are donated;
    the whole Phase II epoch is one XLA program (docs/loops.md)."""
    return jax.jit(distill.make_distill_epoch(
        base_cfg, t_cfg, steps=steps,
        schedule=cosine_schedule(lr, steps, warmup=warmup),
        alpha=alpha, beta=beta, temperature=temperature,
        n_stages=n_stages, vaa_heads=vaa_heads, p_q=p_q,
        optimizer_update=adamw_update, mesh=mesh), donate_argnums=(0, 1))


_TUNE_EPOCH_CACHE: Dict = {}


def _tune_epoch_fn(moe_cfg, mesh, mask, steps, lr, warmup):
    # mask leaves are plain bools, so they can join the key directly
    key = (moe_cfg, mesh, tuple(jax.tree.leaves(mask)), steps, lr, warmup)
    if key not in _TUNE_EPOCH_CACHE:
        if len(_TUNE_EPOCH_CACHE) > 64:
            _TUNE_EPOCH_CACHE.clear()
        _TUNE_EPOCH_CACHE[key] = jax.jit(
            tuning.make_tune_epoch(
                moe_cfg, mask, steps=steps,
                schedule=cosine_schedule(lr, steps, warmup=warmup),
                mesh=mesh), donate_argnums=(0, 1))
    return _TUNE_EPOCH_CACHE[key]


class DeepFusionServer:
    def __init__(self, cfg: ServerConfig, corpus: FederatedCorpus,
                 device_cfgs: Sequence[ModelConfig], *, mesh=None,
                 log: Callable[[str], None] = lambda s: None):
        self.cfg = cfg
        self.corpus = corpus
        self.device_cfgs = list(device_cfgs)
        self.mesh = mesh
        self.log = log
        self.report: Dict = {}

    # ------------------------------------------------------------------
    # Phase I
    # ------------------------------------------------------------------
    def cluster(self, uploads: Sequence[Dict]):
        K = self.cfg.moe_cfg.n_experts
        emb = np.stack([u["embedding"] for u in uploads])
        arch_ids = [u["arch_id"] for u in uploads]
        result = clustering.cluster_devices(emb, K, arch_ids=arch_ids,
                                            seed=self.cfg.seed)
        proxies = proxy.build_proxies([u["params"] for u in uploads], result,
                                      arch_ids)
        self.report["n_clusters"] = len(proxies)
        self.report["cluster_sizes"] = [len(p["members"]) for p in proxies]
        self.log(f"Phase I: {len(uploads)} uploads -> {len(proxies)} proxies "
                 f"{self.report['cluster_sizes']}")
        return proxies, result

    # ------------------------------------------------------------------
    # Phase II
    # ------------------------------------------------------------------
    def distill_proxy(self, proxy_item: Dict, base_cfg: ModelConfig,
                      *, init_params=None, seed_offset: int = 0):
        """Distill one proxy (teacher) into one MoE base model (student)."""
        scfg = self.cfg
        t_cfg = self.device_cfgs[proxy_item["arch"]]
        t_params = proxy_item["params"]
        key = jax.random.PRNGKey(scfg.seed + 101 + seed_offset)
        # copy caller-provided warm starts: the compiled epoch donates its
        # trainable buffers, and donation must never eat a caller's arrays
        s_params = jax.tree.map(jnp.array, init_params) \
            if init_params is not None else M.init_params(key, base_cfg)
        vaa_params = vaa_mod.init_vaa(
            jax.random.PRNGKey(scfg.seed + 202 + seed_offset),
            n_stages=scfg.n_stages, d_student=base_cfg.d_model,
            d_teacher=t_cfg.d_model, d=scfg.vaa_dim, n_heads=scfg.vaa_heads,
            p_q=scfg.p_q)
        trainable = {"student": s_params, "vaa": vaa_params}
        opt = adamw_init(trainable, policy=scfg.state_policy)
        epoch = _distill_epoch_fn(base_cfg, t_cfg, scfg.alpha, scfg.beta,
                                  scfg.temperature, scfg.n_stages,
                                  scfg.vaa_heads, scfg.p_q,
                                  scfg.distill_steps, scfg.distill_lr,
                                  max(scfg.distill_steps // 20, 1), self.mesh)
        batches = self.corpus.mixed_eval_batches(scfg.distill_steps,
                                                 scfg.distill_batch,
                                                 scfg.seq_len)
        trainable, opt, losses = epoch(trainable, opt, t_params, batches)
        hist = [float(x) for x in np.asarray(losses)]
        self.log(f"Phase II: proxy c{proxy_item['cluster']} distilled "
                 f"loss {hist[0]:.3f}->{hist[-1]:.3f}")
        # the finished base model waits in host memory for the Phase III
        # merge: K vocab-wide bases would not fit beside the next
        # proxy's training state on one accelerator
        return jax.device_get(trainable["student"]), hist

    # ------------------------------------------------------------------
    # Phase III
    # ------------------------------------------------------------------
    def merge_and_tune(self, base_params_list: List):
        scfg = self.cfg
        key = jax.random.PRNGKey(scfg.seed + 303)
        moe_params = merge.merge_into_moe(key, scfg.moe_cfg, base_params_list)
        mask, opt = tuning.init_tuning(moe_params)
        self.report["trainable_fraction"] = tuning.trainable_fraction(moe_params)
        self.log(f"Phase III: trainable fraction "
                 f"{self.report['trainable_fraction']:.3f}")
        epoch = _tune_epoch_fn(scfg.moe_cfg, self.mesh, mask, scfg.tune_steps,
                               scfg.tune_lr, max(scfg.tune_steps // 20, 1))
        batches = self.corpus.mixed_eval_batches(scfg.tune_steps,
                                                 scfg.tune_batch,
                                                 scfg.seq_len,
                                                 seed_salt0=10_000)
        moe_params, opt, losses = epoch(moe_params, opt, batches)
        hist = [float(x) for x in np.asarray(losses)]
        self.log(f"Phase III: tune loss {hist[0]:.3f}->{hist[-1]:.3f}")
        return moe_params, hist

    # ------------------------------------------------------------------
    def run(self, uploads: Sequence[Dict]):
        """Full pipeline.  Returns (moe_params, report)."""
        t0 = time.time()
        proxies, _ = self.cluster(uploads)
        base_cfg = merge.base_config_of(self.cfg.moe_cfg)
        bases, distill_hists = [], []
        for i, p in enumerate(proxies):
            s_params, hist = self.distill_proxy(p, base_cfg, seed_offset=i)
            bases.append(s_params)
            distill_hists.append(hist)
        moe_params, tune_hist = self.merge_and_tune(bases)
        self.report["distill_hists"] = distill_hists
        self.report["tune_hist"] = tune_hist
        self.report["comm_bytes"] = int(sum(u["upload_bytes"] for u in uploads))
        self.report["wall_s"] = time.time() - t0
        return moe_params, self.report
