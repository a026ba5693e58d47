"""Training launcher: pjit data+tensor+expert-parallel LM training.

On real hardware this drives the production mesh; on this container it
runs reduced configs on the host mesh.  The same step function is what
the dry-run lowers for the full configs.

  PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b \
      --variant reduced --steps 20 --batch 8 --seq 128

Fleet mode (``--fleet N``) instead drives the federated device fleet —
synchronous one-shot by default, async participation rounds with
``--async-rounds`` — and is what CI's fleet-smoke job exercises under
fake hosts:

  XLA_FLAGS=--xla_force_host_platform_device_count=4 \
  PYTHONPATH=src python -m repro.launch.train --fleet 16 --n-hosts 4 \
      --async-rounds 3 --steps-per-round 4 --dropout 0.25 \
      --deadline-policy stale --straggler-profile mild
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config
from repro.data.federated import FederatedCorpus
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models import model as M
from repro.models.config import ModelConfig
from repro.optim import adamw_init, adamw_update, cosine_schedule
from repro.sharding import named, opt_state_specs, param_specs
from repro.checkpoint import save_pytree


def make_batch(cfg, corpus, step, batch, seq):
    b = corpus.mixed_eval_batch(batch, seq, seed_salt=step)
    if cfg.arch_type == "vlm":
        b["patches"] = jnp.zeros((batch, cfg.frontend_tokens, cfg.d_model),
                                 jnp.dtype(cfg.dtype))
    if cfg.arch_type == "encdec":
        b["frames"] = jnp.zeros((batch, cfg.frontend_tokens, cfg.d_model),
                                jnp.dtype(cfg.dtype))
    return b


# tiny stand-ins for two device families, sized so the fleet smoke runs
# in seconds on CPU
_FLEET_TINY = dict(vocab_size=256, dtype="float32", remat=False,
                   attn_chunk_q=16, attn_chunk_k=16, loss_chunk=16)


def _fleet_families():
    return [
        ModelConfig(name="fleet-gpt2-tiny", n_layers=2, d_model=32,
                    n_heads=2, n_kv_heads=2, head_dim=16, d_ff=64,
                    norm_type="layernorm", act="gelu", mlp_gated=False,
                    pos_embedding="sinusoidal", **_FLEET_TINY).validate(),
        ModelConfig(name="fleet-llama-tiny", n_layers=2, d_model=48,
                    n_heads=2, n_kv_heads=2, head_dim=24, d_ff=96,
                    **_FLEET_TINY).validate(),
    ]


def _uploads_bitwise_equal(ua, ub) -> bool:
    for a, b in zip(ua, ub):
        if a["losses"] != b["losses"]:
            return False
        for xa, xb in zip(jax.tree.leaves(a["params"]),
                          jax.tree.leaves(b["params"])):
            if not bool(jnp.all(xa == xb)):
                return False
    return True


def run_fleet(args) -> int:
    from repro.federated import (STRAGGLER_PROFILES, AsyncFleetConfig,
                                 SimulationConfig, build_fleet, train_fleet,
                                 train_fleet_async)

    sim = SimulationConfig(n_devices=args.fleet, n_domains=4, vocab=256,
                           seq_len=args.seq, device_steps=args.steps,
                           device_batch=args.batch, seed=0)
    corpus = FederatedCorpus.build(seed=sim.seed, n_devices=sim.n_devices,
                                   n_domains=sim.n_domains, vocab=sim.vocab,
                                   alpha=sim.alpha_noniid)
    traffic = STRAGGLER_PROFILES[args.straggler_profile]
    if args.dropout is not None:
        traffic = dataclasses.replace(traffic, dropout_p=args.dropout)
    fleet = build_fleet(sim, corpus, _fleet_families(), traffic=traffic)

    if args.async_rounds <= 0:
        t0 = time.time()
        uploads = train_fleet(fleet, corpus, steps=args.steps,
                              batch=args.batch, seq_len=args.seq,
                              n_hosts=args.n_hosts)
        print(f"sync fleet: {len(uploads)} uploads in {time.time()-t0:.1f}s, "
              f"final losses {[round(u['losses'][-1], 3) for u in uploads[:4]]}…")
        return 0

    acfg = AsyncFleetConfig(
        rounds=args.async_rounds, steps_per_round=args.steps_per_round,
        participation=args.participation, deadline_s=args.deadline_s,
        deadline_policy=args.deadline_policy,
        hierarchical=args.hierarchical)
    t0 = time.time()
    uploads, rep = train_fleet_async(
        fleet, corpus, acfg, batch=args.batch, seq_len=args.seq,
        n_hosts=args.n_hosts, log=print)
    dt = time.time() - t0
    print(f"async fleet ({rep['mode']}): {acfg.rounds} rounds in {dt:.1f}s "
          f"({acfg.rounds / dt:.2f} rounds/s), participation "
          f"{rep['participation_rate']:.2f}, staleness p95 "
          f"{rep['staleness_p95']:.1f}, global comm "
          f"{rep['comm_bytes_global']} B (edge {rep['comm_bytes_edge']} B), "
          f"lost {rep['lost_reports']}")

    if args.check_sync:
        # only meaningful on an ideal fleet: every device online + on
        # time, full participation — then async rounds must reproduce the
        # one-shot synchronous run bit-for-bit
        total = acfg.rounds * acfg.steps_per_round
        ideal = build_fleet(sim, corpus, _fleet_families())
        sync = train_fleet(ideal, corpus, steps=total, batch=args.batch,
                           seq_len=args.seq, n_hosts=args.n_hosts)
        ideal_cfg = dataclasses.replace(acfg, participation=1.0,
                                        deadline_s=float("inf"))
        asy, _ = train_fleet_async(ideal, corpus, ideal_cfg,
                                   batch=args.batch, seq_len=args.seq,
                                   n_hosts=args.n_hosts)
        if not _uploads_bitwise_equal(asy, sync):
            print("CHECK-SYNC FAILED: async rounds != synchronous train_fleet")
            return 1
        print(f"check-sync OK: {acfg.rounds}x{acfg.steps_per_round} async "
              f"rounds == {total}-step train_fleet bit-for-bit")
    return 0


def train_steps(cfg: ModelConfig, mesh, *, steps: int, batch: int, seq: int,
                lr: float, seed: int = 0, moment_policy: str = "",
                log=print):
    """``steps`` AdamW steps of ``cfg`` on ``mesh``; returns (params,
    per-step losses).

    Parameters and optimizer state are created in place with the
    layouts of ``param_specs`` / ``opt_state_specs`` — nothing is built
    whole on one device first — and every step keeps them there.
    ``moment_policy`` is the AdamW moment storage ('' | 'bf16' | 'int8',
    see ``repro.optim.adamw.resolve_moment_policy``).
    """
    corpus = FederatedCorpus.build(seed=0, n_devices=4, n_domains=4,
                                   vocab=cfg.vocab_size)
    key = jax.random.PRNGKey(seed)
    init = lambda: M.init_params(key, cfg)
    init_opt = lambda p: adamw_init(p, policy=moment_policy)
    shapes = jax.eval_shape(init)
    pshard = named(mesh, param_specs(shapes, mesh))
    oshard = named(mesh, opt_state_specs(
        shapes, mesh, state=jax.eval_shape(init_opt, shapes)))
    rep = NamedSharding(mesh, P())
    params = jax.jit(init, out_shardings=pshard)()
    opt = jax.jit(init_opt, out_shardings=oshard)(params)
    sched = cosine_schedule(lr, steps, warmup=max(steps // 20, 1))

    def step_fn(params, opt, batch, lr):
        (loss, metrics), g = jax.value_and_grad(
            lambda p: M.loss_fn(p, cfg, batch, mesh=mesh), has_aux=True)(params)
        params, opt, stats = adamw_update(g, opt, params, lr=lr,
                                          weight_decay=0.01)
        return params, opt, loss, metrics["accuracy"], stats["grad_norm"]

    losses = []
    with mesh:
        jitted = jax.jit(step_fn, out_shardings=(pshard, oshard, rep, rep, rep),
                         donate_argnums=(0, 1))
        t0 = time.time()
        for s in range(steps):
            b = make_batch(cfg, corpus, s, batch, seq)
            params, opt, loss, acc, gn = jitted(params, opt, b, sched(s))
            losses.append(loss)
            if s % max(steps // 10, 1) == 0 or s == steps - 1:
                log(f"step {s:4d} loss {float(loss):.4f} "
                    f"acc {float(acc):.3f} gnorm {float(gn):.2e} "
                    f"({time.time()-t0:.1f}s)")
    return params, [float(x) for x in losses]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--variant", default="reduced",
                    choices=["full", "reduced"])
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--vocab", type=int, default=512)
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--save", default="")
    # fleet mode (see module docstring)
    ap.add_argument("--fleet", type=int, default=0,
                    help="train an N-device federated fleet instead of one model")
    ap.add_argument("--n-hosts", type=int, default=1)
    ap.add_argument("--async-rounds", type=int, default=0,
                    help="> 0 switches the fleet to async participation rounds")
    ap.add_argument("--steps-per-round", type=int, default=4)
    ap.add_argument("--participation", type=float, default=1.0)
    ap.add_argument("--dropout", type=float, default=None,
                    help="per-round dropout probability (overrides profile)")
    ap.add_argument("--deadline-s", type=float, default=float("inf"))
    ap.add_argument("--deadline-policy", default="stale",
                    choices=["drop", "stale", "standby"])
    ap.add_argument("--straggler-profile", default="none",
                    choices=["none", "mild", "harsh"])
    ap.add_argument("--hierarchical", action="store_true")
    ap.add_argument("--check-sync", action="store_true",
                    help="assert async rounds on an ideal fleet reproduce "
                         "synchronous train_fleet bit-for-bit")
    args = ap.parse_args()
    enable_compile_cache()

    if args.fleet > 0:
        raise SystemExit(run_fleet(args))
    if not args.arch:
        ap.error("--arch is required (unless running --fleet mode)")

    cfg = get_config(args.arch, variant=args.variant)
    if args.variant == "reduced":
        cfg = cfg.replace(vocab_size=args.vocab)
    mesh = (make_production_mesh() if args.production_mesh
            else make_host_mesh())
    params, _ = train_steps(cfg, mesh, steps=args.steps, batch=args.batch,
                            seq=args.seq, lr=args.lr)
    if args.save:
        save_pytree(params, args.save)
        print("saved", args.save)


if __name__ == "__main__":
    main()
