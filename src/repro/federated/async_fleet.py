"""Async / hierarchical fleet rounds with straggler + dropout dynamics.

`train_fleet` trains every device's whole local run as one synchronous
pass; real edge fleets don't work like that — devices go offline, report
late, and the server cannot wait for the slowest phone on the planet.
This driver simulates the paper's deployment story at fleet scale:

* **Rounds.**  Local training is cut into ``rounds`` rounds of
  ``steps_per_round`` steps.  Devices keep their OWN params between
  rounds (DeepFusion is one-shot FL — there is no global pull-down), so
  with every device online in every round the final per-device params
  are bit-identical to a single `train_fleet` run of the same total
  steps: the per-round scan computes exactly steps ``[r*k, (r+1)*k)`` of
  the same schedule over the same batch stream.

* **Participation + stragglers.**  Each round a seeded subset of the
  fleet is selected to report (``AsyncFleetConfig.participation``);
  every online device trains, but only delivered reports reach the
  server.  ``DeviceSpec.traffic`` (dropout, lognormal latency,
  availability windows) decides who is online and who misses the
  ``deadline_s`` — late reports follow ``deadline_policy`` (drop /
  carry-as-stale / standby over-selection).  All draws are pure
  functions of ``(seed, device, round)``, so runs replay bit-identically
  and a dropped device's batch stream continues exactly where it paused.

* **Merging.**  Delivered reports merge per arch bucket through
  ``server.FleetAggregator`` with FedAsync staleness discounts
  ``alpha / (1 + staleness)^a``.  ``hierarchical=True`` routes device
  reports to per-bucket sub-servers and ships only each bucket's
  aggregate across the global link — same merge math, cheaper WAN.

* **Comm accounting** bills only devices that actually delivered a
  report that round (`device_upload_bytes` of the *configured* model,
  Fig. 8 style); hierarchical mode splits edge-tier vs global-tier
  bytes.

* **Multi-host.**  ``n_hosts > 1`` shards every bucket's stacked device
  axis over a ``("hosts",)`` mesh (``sharding.rules.fleet_specs``), so
  the resident fleet state per host — and with it the fleet size one
  simulation sustains — scales linearly with hosts.

Compilation: one executable per (bucket cfg, bucket size) for the whole
run — offline devices are masked inside the vmapped round program, not
sliced out of it, so the participant set never changes the shapes.
"""
from __future__ import annotations

import math
from typing import Callable, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.federated import FederatedCorpus
from repro.federated.device import (DeviceSpec, _fleet_round_fn,
                                    _init_bucket, _pad_lanes,
                                    _shard_bucket, _stack_trees, _upload,
                                    device_upload_bytes, fleet_buckets,
                                    model_param_bytes, sample_traffic)
from repro.federated.server import AsyncFleetConfig, FleetAggregator


def _zeros_like_batches(batches):
    return jax.tree.map(lambda x: np.zeros_like(np.asarray(x)), batches)


def train_fleet_async(fleet: Sequence[DeviceSpec], corpus: FederatedCorpus,
                      acfg: AsyncFleetConfig, *, batch: int, seq_len: int,
                      lr: float = 3e-3, seed: int = 0,
                      state_policy: str = "", n_hosts: int = 1, mesh=None,
                      log: Callable[[str], None] = lambda s: None
                      ) -> Tuple[List[Dict], Dict]:
    """Returns ``(uploads, fleet_report)``.

    ``uploads`` matches `train_fleet`'s contract (fleet order, same
    ``_upload`` payloads — a device's ``losses`` only cover the rounds
    it actually trained).  ``fleet_report`` carries the per-round
    simulation log: participation, staleness histogram, effective comm
    bytes, and the per-bucket staleness-merged aggregates.
    """
    acfg.validate()
    if mesh is None and n_hosts > 1:
        from repro.launch.mesh import make_fleet_mesh
        mesh = make_fleet_mesh(n_hosts)
    n_shards = mesh.shape["hosts"] if mesh is not None else 1

    k = acfg.steps_per_round
    total_steps = acfg.rounds * k
    warmup = max(total_steps // 20, 1)
    n_fleet = len(fleet)
    by_id = {s.device_id: s for s in fleet}

    buckets = fleet_buckets(fleet)
    state: Dict = {}
    for cfg, specs in buckets.items():
        params, opt = _init_bucket(specs, seed, state_policy)
        state[cfg] = {"specs": specs, "params": params, "opt": opt}
    local_step = {s.device_id: 0 for s in fleet}
    losses: Dict[int, List[float]] = {s.device_id: [] for s in fleet}

    aggregator = FleetAggregator(acfg)
    pending: List[Dict] = []     # late reports carried across rounds
    rounds_log: List[Dict] = []
    comm_global = 0
    comm_edge = 0
    lost_reports = 0

    for r in range(acfg.rounds):
        traffic = {s.device_id: sample_traffic(s, r, acfg.seed)
                   for s in fleet}
        online = {d: t[1] for d, t in traffic.items()}

        # -- participation sampling (seeded, fleet-order independent) --
        target = max(1, math.ceil(acfg.participation * n_fleet))
        n_sel = target
        if acfg.deadline_policy == "standby":
            n_sel = min(n_fleet, math.ceil(target * (1 + acfg.over_select)))
        if n_sel >= n_fleet:
            selected = {s.device_id for s in fleet}
        else:
            rng = np.random.default_rng((acfg.seed, 424_242, r))
            ids = sorted(by_id)
            selected = set(np.asarray(ids)[
                rng.choice(n_fleet, size=n_sel, replace=False)].tolist())

        # -- every online device trains its round (one program/bucket) --
        for cfg, st in state.items():
            specs = st["specs"]
            active = np.array([online[s.device_id] for s in specs])
            per_dev = [
                corpus.device_batches(s.device_id, k, batch, seq_len,
                                      start=local_step[s.device_id])
                if online[s.device_id] else None for s in specs]
            proto = next((b for b in per_dev if b is not None), None)
            if proto is None:           # whole bucket offline this round
                continue
            zero = _zeros_like_batches(proto)
            batches = _stack_trees([b if b is not None else zero
                                    for b in per_dev])
            starts = jnp.asarray([local_step[s.device_id] for s in specs],
                                 jnp.int32)
            active_j = jnp.asarray(active)
            params, opt = st["params"], st["opt"]
            if mesh is not None:
                n_pad = (-len(specs)) % n_shards
                params, opt, batches, starts, active_j = (
                    _pad_lanes(t, n_pad)
                    for t in (params, opt, batches, starts, active_j))
                params, opt, batches, starts, active_j = _shard_bucket(
                    mesh, params, opt, batches, starts, active_j)
            round_fn = _fleet_round_fn(cfg, k, lr, warmup, total_steps)
            params, opt, l = round_fn(params, opt, batches, starts, active_j)
            if mesh is not None and len(specs) % n_shards:
                # drop this round's padding before the state is carried
                # into the next round (which pads afresh)
                params, opt = (jax.tree.map(lambda x: x[:len(specs)], t)
                               for t in (params, opt))
            st["params"], st["opt"] = params, opt
            l = np.asarray(l)[:len(specs)]
            for i, s in enumerate(specs):
                if online[s.device_id]:
                    losses[s.device_id].extend(float(x) for x in l[i])
                    local_step[s.device_id] += k

        # -- reports: selected ∩ online devices ship their fresh state --
        fresh, n_late_dropped = [], 0
        for cfg, st in state.items():
            for i, s in enumerate(st["specs"]):
                d = s.device_id
                if d not in selected or not online[d]:
                    continue
                latency = traffic[d][0]
                late_by = (0 if latency <= acfg.deadline_s
                           else int(math.ceil(latency / acfg.deadline_s)) - 1)
                if late_by and acfg.deadline_policy in ("drop", "standby"):
                    n_late_dropped += 1
                    lost_reports += 1
                    continue
                report = {
                    "device_id": d,
                    "bucket": cfg,
                    "params": jax.tree.map(lambda x: x[i], st["params"]),
                    "trained_round": r,
                    "arrival_round": r + late_by,
                    "bytes": device_upload_bytes(s.comm_cfg),
                }
                if late_by:
                    pending.append(report)
                else:
                    fresh.append(report)

        # -- merge everything deliverable this round, per bucket --
        matured = [p for p in pending if p["arrival_round"] <= r]
        pending = [p for p in pending if p["arrival_round"] > r]
        deliverable = fresh + matured
        per_bucket: Dict = {}
        for rep in deliverable:
            rep["staleness"] = r - rep["trained_round"]
            per_bucket.setdefault(rep["bucket"], []).append(rep)
        round_bytes = 0
        for cfg, reps in per_bucket.items():
            aggregator.merge_round(cfg, reps)
            dev_bytes = sum(rep["bytes"] for rep in reps)
            if acfg.hierarchical:
                # devices -> sub-server rides the cheap edge tier; only
                # the bucket aggregate crosses the global link (billed at
                # the bucket's configured full-size model, Fig. 8 style)
                comm_edge += dev_bytes
                agg_bytes = model_param_bytes(
                    by_id[reps[0]["device_id"]].comm_cfg)
                comm_global += agg_bytes
                round_bytes += agg_bytes
            else:
                comm_global += dev_bytes
                round_bytes += dev_bytes

        stale_merged = len(matured)
        n_online = sum(online.values())
        n_reported = len(deliverable)
        rounds_log.append({
            "round": r,
            "online": n_online,
            "selected": len(selected),
            "reported": n_reported,
            "stale_merged": stale_merged,
            "late_dropped": n_late_dropped,
            "participation_rate": round(n_reported / n_fleet, 4),
            "comm_bytes": int(round_bytes),
        })
        log(f"round {r}: online {n_online}/{n_fleet}, selected "
            f"{len(selected)}, reported {n_reported} "
            f"({stale_merged} stale, {n_late_dropped} late-dropped), "
            f"{round_bytes} B")

    lost_reports += len(pending)     # never matured before the run ended
    staleness = aggregator.merged_staleness
    uploads = []
    for s in fleet:
        i = state[s.cfg]["specs"].index(s)
        uploads.append(_upload(
            s, corpus, jax.tree.map(lambda x: x[i], state[s.cfg]["params"]),
            np.asarray(losses[s.device_id], np.float32)))

    fleet_report = {
        "mode": "hierarchical" if acfg.hierarchical else "flat",
        "rounds": rounds_log,
        "participation_rate": round(
            float(np.mean([x["participation_rate"] for x in rounds_log])), 4),
        "staleness_hist": aggregator.staleness_histogram(),
        "staleness_p95": (float(np.percentile(staleness, 95))
                          if staleness else 0.0),
        "merged_reports": len(staleness),
        "lost_reports": int(lost_reports),
        "comm_bytes_global": int(comm_global),
        "comm_bytes_edge": int(comm_edge),
        "aggregates": {cfg.name: aggregator.aggregates[cfg]
                       for cfg in aggregator.aggregates},
        "n_hosts": n_shards,
    }
    return uploads, fleet_report
