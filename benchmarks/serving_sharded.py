"""Sharded serving benchmark: decode-mesh engine vs single device, and
the EP-A2A overlap win.

This is a CPU rehearsal, not a chip measurement: the child always runs
on the host CPU with 8 fake devices (``JAX_PLATFORMS=cpu``), even on a
machine with a TPU, and its row records the platform it ran on.  Its
tok/s are CPU wall-clock numbers.

The measurement needs a multi-device jax runtime, but the bench runner
process has usually initialised jax single-device already (XLA_FLAGS
cannot be applied after backend init) — so ``serving_sharded_bench``
re-execs THIS module as a child with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` and parses the
row the child prints.  Only the child imports jax.

Modes (identical Poisson traffic, greedy, token-identical asserted):

  single          : ServeEngine, mesh=None
  sharded         : ServeEngine on ``make_decode_mesh()`` (data=2, model=4)
  sharded_overlap : same, ``cfg.overlap_a2a=True`` (half-batch EP-A2A
                    overlap) — the compiled decode step's HLO is checked
                    with ``hlo_analysis.assert_a2a_overlap``

Appends the "sharded" row to BENCH_serve.json.  ``speedup_overlap``
(overlap-on vs overlap-off tok/s, same run, same machine) is the
regression-gated metric; absolute tok/s is informational.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

_MARK = "BENCH_SHARDED_JSON:"
_N_DEVICES = 8


def serving_sharded_bench(log=print):
    """Parent entry: run the measurement in a fresh 8-device child and
    append its row to BENCH_serve.json."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        f" --xla_force_host_platform_device_count={_N_DEVICES}"
                        ).strip()
    env["JAX_PLATFORMS"] = "cpu"
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.path.join(root, "src"), root,
                    env.get("PYTHONPATH", "")) if p)
    proc = subprocess.run([sys.executable, "-m", "benchmarks.serving_sharded"],
                          capture_output=True, text=True, env=env, cwd=root,
                          timeout=1800)
    if proc.returncode != 0:
        raise RuntimeError(f"sharded serving child failed:\n{proc.stdout}\n"
                           f"{proc.stderr}")
    row = None
    for line in proc.stdout.splitlines():
        if line.startswith(_MARK):
            row = json.loads(line[len(_MARK):])
        elif line.strip():
            log(f"  {line}")
    if row is None:
        raise RuntimeError(f"child emitted no row:\n{proc.stdout}")

    path = os.path.join(root, "BENCH_serve.json")
    payload = {}
    if os.path.exists(path):
        with open(path) as f:
            payload = json.load(f)
    payload["sharded"] = row
    with open(path, "w") as f:
        json.dump(payload, f, indent=1)
    log(f"  sharded: mesh={row['mesh']} "
        f"{row['modes']['sharded']['tok_s']} tok/s, overlap win "
        f"{row['speedup_overlap']}x (outputs match single-device)")
    return row


def _child_main(n_requests: int = 8, n_slots: int = 4, seg_len: int = 4,
                seed: int = 0, arch: str = "qwen2-moe-a2.7b",
                repeats: int = 2):
    import functools
    import time

    import jax
    import jax.numpy as jnp

    from benchmarks.serving import (_serve_engine_mode, _timed_replays,
                                    _traffic)
    from repro.configs import get_config
    from repro.launch import hlo_analysis as H
    from repro.launch.mesh import make_decode_mesh
    from repro.models import model as M
    from repro.serve import ServeEngine

    assert len(jax.devices()) == _N_DEVICES, jax.devices()
    cfg = get_config(arch, variant="reduced").replace(vocab_size=256)
    ocfg = cfg.replace(overlap_a2a=True)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    batches, lengths, arrivals = _traffic(cfg, n_requests, seed)
    max_len = max(M.decode_capacity(cfg, p, g) for p, g in lengths)
    total_tokens = sum(g for _, g in lengths)
    mesh = make_decode_mesh()

    # structural proof first: the overlapped decode step's compiled HLO
    # has an all-to-all with dataflow-independent matmul work
    with mesh:
        ecfg = ServeEngine(params, ocfg, n_slots=n_slots, max_len=max_len,
                           mesh=mesh).cfg  # engine-forced moe_dropless
        cache = M.init_decode_cache(ecfg, n_slots, max_len, mesh=mesh)
        step = jax.jit(lambda p, c, t, q, lv: M.decode_step(
            p, ecfg, c, t, q, mesh=mesh, live=lv))
        hlo = step.lower(params, cache, jnp.zeros((n_slots, 1), jnp.int32),
                         jnp.zeros((n_slots,), jnp.int32),
                         jnp.ones((n_slots,), jnp.bool_)).compile().as_text()
    H.assert_a2a_overlap(hlo)
    n_indep = max(n for _, _, n in H.a2a_overlap_pairs(hlo))

    results, outputs = {}, {}
    for name, (mcfg, msh) in {
        "single": (cfg, None),
        "sharded": (cfg, mesh),
        "sharded_overlap": (ocfg, mesh),
    }.items():
        eng = ServeEngine(params, mcfg, n_slots=n_slots, max_len=max_len,
                          seg_len=seg_len, mesh=msh)
        fn = functools.partial(_serve_engine_mode, engine=eng)
        wall, outs, extra = _timed_replays(
            fn, params, mcfg, batches, lengths, arrivals, max_len,
            total_tokens, name, repeats)
        n_tok = sum(len(v) for v in outs.values())
        results[name] = {"wall_s": round(wall, 4),
                         "tok_s": round(n_tok / wall, 2), **extra}
        outputs[name] = outs
        print(f"{name}: {n_tok} tok in {wall:.3f}s "
              f"({results[name]['tok_s']} tok/s)")
    # greedy + dropless expert buffers: every mode must emit the SAME
    # tokens — a sharded speedup over diverging outputs is meaningless
    assert outputs["sharded"] == outputs["single"], \
        "sharded engine diverged from single-device"
    assert outputs["sharded_overlap"] == outputs["single"], \
        "overlapped engine diverged from single-device"

    dev = jax.devices()[0]
    row = {
        "arch": cfg.name,
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "mesh": {"data": mesh.shape["data"], "model": mesh.shape["model"]},
        "traffic": {"n_requests": n_requests, "seed": seed,
                    "total_tokens": total_tokens},
        "engine": {"n_slots": n_slots, "seg_len": seg_len,
                   "max_len": max_len},
        "modes": results,
        "outputs_match_single_device": True,
        "overlap_independent_dots": n_indep,
        # same-run, same-machine ratio: the regression-gated metric
        "speedup_overlap": round(
            results["sharded_overlap"]["tok_s"] / results["sharded"]["tok_s"],
            2),
    }
    print(_MARK + json.dumps(row))


if __name__ == "__main__":
    _child_main()
