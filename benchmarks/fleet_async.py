"""Async fleet benchmark: participation rounds + multi-host scaling.

This is a CPU rehearsal, not a chip measurement: the child always runs
on the host CPU with 4 fake hosts (``JAX_PLATFORMS=cpu``), even on a
machine with a TPU, and its row records the platform it ran on.  Its
wall-clock numbers are CPU times.

Like ``serving_sharded``, the measurement needs a multi-device jax
runtime (4 fake hosts), so ``fleet_async_bench`` re-execs THIS module
as a child under ``XLA_FLAGS=--xla_force_host_platform_device_count=4``
and parses the row the child prints.  Only the child imports jax.

Measured (N=16 devices over the two bench families, 3 rounds):

  sync              : one-shot ``train_fleet`` over the same total steps
  async_ideal       : async rounds, dropout=0, full participation —
                      asserted bit-for-bit equal to sync, and
                      ``stale_merge_overhead`` = t_async / t_sync is the
                      price of round-slicing the scan (gated LOWER)
  async_stragglers  : dropout=0.25 + mild latency under a stale-merge
                      deadline — participation_rate (gated HIGHER),
                      staleness p95, rounds/s
  devices_per_host_scaling : host-resident fleet state bytes at 1 host
                      / at 4 hosts (``sharding.host_resident_bytes``) —
                      the multi-host capacity claim, gated HIGHER with a
                      >= 1.8x floor asserted in-bench

Merges the row into BENCH_fleet.json under "fleet_async" (read-modify-
write — the fleet_scaling columns are preserved).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

_MARK = "BENCH_FLEET_ASYNC_JSON:"
_N_HOSTS = 4
_MIN_HOST_SCALING = 1.8


def fleet_async_bench(log=print):
    """Parent entry: run the measurement in a fresh 4-host child and
    merge its row into BENCH_fleet.json."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        f" --xla_force_host_platform_device_count={_N_HOSTS}"
                        ).strip()
    env["JAX_PLATFORMS"] = "cpu"
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.path.join(root, "src"), root,
                    env.get("PYTHONPATH", "")) if p)
    proc = subprocess.run([sys.executable, "-m", "benchmarks.fleet_async"],
                          capture_output=True, text=True, env=env, cwd=root,
                          timeout=1800)
    if proc.returncode != 0:
        raise RuntimeError(f"fleet async child failed:\n{proc.stdout}\n"
                           f"{proc.stderr}")
    row = None
    for line in proc.stdout.splitlines():
        if line.startswith(_MARK):
            row = json.loads(line[len(_MARK):])
        elif line.strip():
            log(f"  {line}")
    if row is None:
        raise RuntimeError(f"child emitted no row:\n{proc.stdout}")

    path = os.path.join(root, "BENCH_fleet.json")
    payload = {}
    if os.path.exists(path):
        with open(path) as f:
            payload = json.load(f)
    payload["fleet_async"] = row
    with open(path, "w") as f:
        json.dump(payload, f, indent=1)
    log(f"  fleet_async: ideal overhead "
        f"{row['modes']['async_ideal']['stale_merge_overhead']}x, "
        f"straggler participation "
        f"{row['modes']['async_stragglers']['participation_rate']}, "
        f"host scaling {row['devices_per_host_scaling']}x")
    return row


def _child_main(n_devices: int = 16, rounds: int = 3,
                steps_per_round: int = 4, seed: int = 0):
    import dataclasses
    import time

    import jax
    import numpy as np

    from benchmarks.common import SEQ, device_families, sim_cfg
    from repro.data.federated import FederatedCorpus
    from repro.federated import (STRAGGLER_PROFILES, AsyncFleetConfig,
                                 build_fleet, train_fleet,
                                 train_fleet_async)
    from repro.federated.device import (_device_init, _pad_lanes,
                                        _shard_bucket, _stack_trees,
                                        fleet_buckets)
    from repro.launch.mesh import make_fleet_mesh
    from repro.sharding import host_resident_bytes

    assert len(jax.devices()) == _N_HOSTS, jax.devices()
    sim = sim_cfg(n_devices, seed)
    total = rounds * steps_per_round
    batch = sim.device_batch
    corpus = FederatedCorpus.build(seed=seed, n_devices=n_devices,
                                   n_domains=sim.n_domains, vocab=sim.vocab,
                                   alpha=sim.alpha_noniid)
    fleet = build_fleet(sim, corpus, device_families())
    kw = dict(batch=batch, seq_len=SEQ, seed=seed)

    def best_of(fn, n=2):
        """(best wall_s, last result) — best-of-n damps scheduler noise,
        the gated overhead ratio needs stable numerators."""
        best, out = float("inf"), None
        for _ in range(n):
            t0 = time.perf_counter()
            out = fn()
            best = min(best, time.perf_counter() - t0)
        return best, out

    # -- sync baseline (same total steps) ------------------------------
    train_fleet(fleet, corpus, steps=total, **kw)            # warmup
    t_sync, sync_ups = best_of(
        lambda: train_fleet(fleet, corpus, steps=total, **kw))

    # -- async, ideal fleet: must reproduce sync bit-for-bit -----------
    ideal = AsyncFleetConfig(rounds=rounds, steps_per_round=steps_per_round)
    train_fleet_async(fleet, corpus, ideal, **kw)            # warmup
    t_async, (async_ups, ideal_rep) = best_of(
        lambda: train_fleet_async(fleet, corpus, ideal, **kw))
    for a, s in zip(async_ups, sync_ups):
        assert a["losses"] == s["losses"]
        for xa, xs in zip(jax.tree.leaves(a["params"]),
                          jax.tree.leaves(s["params"])):
            assert (np.asarray(xa) == np.asarray(xs)).all(), \
                "async ideal fleet diverged from synchronous train_fleet"
    print(f"ideal: {rounds}x{steps_per_round} async rounds == {total}-step "
          f"train_fleet bit-for-bit ({t_async:.2f}s vs {t_sync:.2f}s sync)")

    # -- async with stragglers -----------------------------------------
    strag_fleet = build_fleet(sim, corpus, device_families(),
                              traffic=dataclasses.replace(
                                  STRAGGLER_PROFILES["mild"],
                                  dropout_p=0.25))
    strag = AsyncFleetConfig(rounds=rounds, steps_per_round=steps_per_round,
                             deadline_s=1.0, deadline_policy="stale")
    train_fleet_async(strag_fleet, corpus, strag, **kw)      # warmup
    t0 = time.perf_counter()
    _, srep = train_fleet_async(strag_fleet, corpus, strag, **kw)
    t_strag = time.perf_counter() - t0

    # -- multi-host resident-state scaling -----------------------------
    mesh = make_fleet_mesh(_N_HOSTS)
    b1 = b4 = 0
    for cfg, specs in fleet_buckets(fleet).items():
        inits = [_device_init(s, seed, "") for s in specs]
        params = _stack_trees([p for p, _ in inits])
        opt = _stack_trees([o for _, o in inits])
        b1 += host_resident_bytes(params) + host_resident_bytes(opt)
        n_pad = (-len(specs)) % _N_HOSTS
        params, opt = (_pad_lanes(t, n_pad) for t in (params, opt))
        params, opt = _shard_bucket(mesh, params, opt)
        b4 += host_resident_bytes(params) + host_resident_bytes(opt)
    scaling = round(b1 / max(b4, 1), 2)
    assert scaling >= _MIN_HOST_SCALING, \
        (f"devices_per_host_scaling {scaling} < {_MIN_HOST_SCALING}: "
         f"sharding the stacked fleet over {_N_HOSTS} hosts kept too much "
         f"state resident per host")
    print(f"host scaling: {b1} B resident at 1 host vs {b4} B at "
          f"{_N_HOSTS} hosts ({scaling}x)")

    dev = jax.devices()[0]
    row = {
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "n_devices": n_devices,
        "rounds": rounds,
        "steps_per_round": steps_per_round,
        "n_hosts": _N_HOSTS,
        "modes": {
            "sync": {"wall_s": round(t_sync, 3)},
            "async_ideal": {
                "wall_s": round(t_async, 3),
                "rounds_per_s": round(rounds / max(t_async, 1e-9), 3),
                "stale_merge_overhead": round(t_async / max(t_sync, 1e-9),
                                              2),
                "participation_rate": ideal_rep["participation_rate"],
                "bitwise_equals_sync": True,
            },
            "async_stragglers": {
                "wall_s": round(t_strag, 3),
                "rounds_per_s": round(rounds / max(t_strag, 1e-9), 3),
                "participation_rate": srep["participation_rate"],
                "staleness_p95": srep["staleness_p95"],
                "stale_merged": sum(r["stale_merged"]
                                    for r in srep["rounds"]),
                "lost_reports": srep["lost_reports"],
                "comm_bytes_global": srep["comm_bytes_global"],
            },
        },
        "devices_per_host_scaling": scaling,
        "note": ("stale_merge_overhead = async-ideal / sync wall clock at "
                 "equal total steps (round-slicing the compiled scan); "
                 "devices_per_host_scaling = host-resident fleet state at "
                 "1 host / at 4 hosts (fleet_specs sharding), both "
                 "machine-independent and regression-gated."),
    }
    print(_MARK + json.dumps(row))


if __name__ == "__main__":
    _child_main()
