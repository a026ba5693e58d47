#!/usr/bin/env python3
"""Run the DeepFusion main path once on a TPU, at Qwen1.5-MoE-A2.7B widths.

  python3 chip_smoke.py              # one chip: (a) train, (b) serve
  python3 chip_smoke.py --chips 4    # four chips: expert-parallel training
                                     # and sharded decode, each checked
                                     # against the same work on one chip

(a) ``run_deepfusion``: four edge devices (GPT-2 and GPT-2-Medium) train
    locally, Phase I clusters their uploads, Phase II distills each
    proxy into a dense base model through the fused KD loss, Phase III
    merges the bases into the global MoE and tunes it, then the eval.
(b) The tuned MoE serves four greedy requests (128-512 token prompts
    sharing a 128-token prefix, 32 new tokens each) through
    ``PagedServeEngine`` with bucketed chunked admission; the tokens must
    equal the contiguous ``ServeEngine``'s on the same traffic.  Both
    engines serve in float32.

Weights and data are random, drawn from ``--seed``.  Every width is the
published one; depth and the serving check's dtype are cut, and each
cut is printed under ``reduced``.  Each part prints its wall and compile
seconds, its losses, the Pallas kernels compiled into its programs
(``tpu_custom_call``) and the device's peak memory.  No rate,
MFU or roofline share is printed.  The last line of stdout is one JSON
object naming the device.  Without a TPU the script exits non-zero and
prints no result.
"""
from __future__ import annotations

import argparse
import json
import logging
import math
import os
import shutil
import sys
import time
from collections import Counter

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.federated import (ServerConfig, SimulationConfig,  # noqa: E402
                             run_deepfusion)
from repro.launch import hlo_analysis  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_decode_mesh, make_host_mesh  # noqa: E402
from repro.launch.train import train_steps  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.models.config import ModelConfig  # noqa: E402
from repro.serve import PagedServeEngine, ServeEngine  # noqa: E402

# Depth that fits one v5e (16 GiB HBM), from memory_analysis() of the
# programs compiled for a described v5e at these widths:
#  * Phase III tune epoch of the global MoE: 1 layer needs 10.5 GiB;
#    2 layers need 16.0 GiB, past the 15.75 GiB XLA may use.
#  * the vmapped fleet bucket of two GPT-2-Medium devices at vocab
#    151936: 24 layers need 17.1 GiB, 16 layers 13.2 GiB.
MOE_LAYERS = 1
GPT2_MEDIUM_LAYERS = 16
# Steps of the --chips 4 training check must agree per step within this
# absolute loss difference: both runs are bf16 with f32 accumulation, and
# the expert-parallel run sums in another order (a2a + sharded matmuls).
LOSS_TOL = 2e-2
# kernels the main path must compile into its programs on the chip
EXPECTED_KERNELS = {
    "phase II distill": {"_kd_kernel"},
    "phase III tune": {"_kd_kernel", "_ffn_kernel"},
    "serve paged": {"_paged_kernel"},
}


def log_line(msg: str) -> None:
    print(msg, flush=True)


class Recorder:
    """Splits a run into named parts at ``mark`` calls.

    For each part: wall seconds, compile seconds (the union of JAX's
    trace / lower / backend-compile spans inside it), and the Pallas
    kernels of the programs compiled in it, read from the lowered
    modules JAX dumps to ``ir_dir`` (None: no kernel census, e.g. on
    CPU)."""

    def __init__(self, ir_dir=None):
        self.ir_dir = ir_dir
        self.parts = {}
        self._spans = []
        self._seen = set()
        self._t = time.time()
        jax.monitoring.register_event_time_span_listener(self._on_span)

    def close(self) -> None:
        jax.monitoring.unregister_event_time_span_listener(self._on_span)

    def _on_span(self, event, start, end, **_):
        if event.startswith("/jax/core/compile/"):
            self._spans.append((start, end))

    def _compile_seconds(self, t0, t1) -> float:
        total, cur = 0.0, t0
        for s, e in sorted(self._spans):
            s, e = max(s, cur), min(e, t1)
            if e > s:
                total += e - s
                cur = e
        return total

    def _new_kernels(self):
        if self.ir_dir is None or not os.path.isdir(self.ir_dir):
            return {}
        found = {}
        for name in sorted(os.listdir(self.ir_dir)):
            if name in self._seen or not name.endswith("_compile.mlir"):
                continue
            self._seen.add(name)
            with open(os.path.join(self.ir_dir, name)) as f:
                ks = hlo_analysis.tpu_kernels(f.read())
            if ks:
                module = name.split("_", 2)[2][:-len("_compile.mlir")]
                found[f"{name[6:10]}:{module}"] = ks
        return found

    def mark(self, part: str, **info):
        t1 = time.time()
        wall = t1 - self._t
        comp = self._compile_seconds(self._t, t1)
        kernels = self._new_kernels()
        self._t = t1
        rec = self.parts.setdefault(part, {"wall_s": 0.0, "compile_s": 0.0,
                                           "kernels": Counter(), "n": 0})
        rec["wall_s"] += wall
        rec["compile_s"] += comp
        rec["n"] += 1
        for ks in kernels.values():
            rec["kernels"].update(ks)
        extra = " ".join(f"{k} {v}" for k, v in info.items()
                         if v is not None)
        log_line(f"[{part}] wall {wall:.1f}s compile {comp:.1f}s "
                 f"{extra}".rstrip())
        for module, ks in kernels.items():
            log_line(f"  tpu_custom_call x{sum(ks.values())} in {module}: "
                     + ", ".join(f"{k} x{n}" for k, n in sorted(ks.items())))
        return rec


def peak_bytes():
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _check_finite(name: str, values) -> None:
    bad = [v for v in values if not math.isfinite(v)]
    if bad or not values:
        raise RuntimeError(f"{name}: losses not finite: {values}")


# ---------------------------------------------------------------------------
# configurations
# ---------------------------------------------------------------------------

def smoke_configs():
    """(global MoE, device families, cuts of the MoE and the serving
    check, cuts of the device models) at published widths."""
    moe = get_config("qwen2-moe-a2.7b").replace(n_layers=MOE_LAYERS,
                                                use_pallas=True)
    vocab = moe.vocab_size
    gpt2 = get_config("gpt2").replace(vocab_size=vocab, use_pallas=True)
    gpt2m = get_config("gpt2-medium").replace(
        vocab_size=vocab, n_layers=GPT2_MEDIUM_LAYERS, use_pallas=True)
    moe_cuts = [
        f"qwen2-moe-a2.7b: n_layers 24 -> {MOE_LAYERS} (Phase III tune step "
        f"fits one chip at 1 layer, not at 2)",
        "serving check: bfloat16 -> float32 (bf16 rounding flips near-tie "
        "greedy tokens between the paged and contiguous engines)",
    ]
    device_cuts = [
        f"gpt2-medium: n_layers 24 -> {GPT2_MEDIUM_LAYERS} (its fleet bucket "
        f"of two devices fits one chip at 16 layers, not at 24)",
        f"gpt2, gpt2-medium: vocab 50257 -> {vocab} (the KD loss needs the "
        f"MoE's vocabulary on both sides)",
    ]
    return moe, (gpt2, gpt2m), moe_cuts, device_cuts


# ---------------------------------------------------------------------------
# (a) train: the federated pipeline
# ---------------------------------------------------------------------------

def train_phase(moe_cfg: ModelConfig, device_cfgs, rec: Recorder, *,
                seed: int = 0, seq_len: int = 256, steps: int = 4,
                batch: int = 4):
    """``run_deepfusion`` with 4 devices over 4 domains; returns
    (tuned MoE params, report).  Fails unless every loss is finite."""
    sim = SimulationConfig(n_devices=4, n_domains=4,
                           vocab=moe_cfg.vocab_size, seq_len=seq_len,
                           device_steps=steps, device_batch=batch, seed=seed)
    server = ServerConfig(moe_cfg=moe_cfg, distill_steps=steps,
                          distill_batch=batch, tune_steps=steps,
                          tune_batch=batch, seq_len=seq_len, seed=seed)
    state = {"fleet": False}

    def log(msg: str) -> None:
        log_line(msg)
        if msg.startswith("device ") and not state["fleet"]:
            state["fleet"] = True
            rec.mark("fleet local training", peak_bytes=peak_bytes())
        elif msg.startswith("Phase I:"):
            rec.mark("phase I cluster")
        elif msg.startswith("Phase II: proxy"):
            rec.mark("phase II distill", peak_bytes=peak_bytes())
        elif msg.startswith("Phase III: trainable"):
            rec.mark("phase III merge")
        elif msg.startswith("Phase III: tune loss"):
            rec.mark("phase III tune", peak_bytes=peak_bytes())
        elif msg.startswith("global MoE"):
            rec.mark("eval", peak_bytes=peak_bytes())

    params, report = run_deepfusion(sim, server, device_cfgs, log=log)
    for up in report["uploads"]:
        _check_finite(f"device {up['device_id']}", up["losses"])
    for i, hist in enumerate(report["distill_hists"]):
        _check_finite(f"phase II proxy {i}", hist)
    _check_finite("phase III tune", report["tune_hist"])
    _check_finite("eval log-ppl", [report["metrics"]["log_ppl"]])
    log_line(
        "losses (first -> last): "
        + "; ".join(f"device {u['device_id']} {u['losses'][0]:.4f} -> "
                    f"{u['losses'][-1]:.4f}" for u in report["uploads"])
        + "; " + "; ".join(f"phase II proxy {i} {h[0]:.4f} -> {h[-1]:.4f}"
                           for i, h in enumerate(report["distill_hists"]))
        + f"; phase III {report['tune_hist'][0]:.4f} -> "
          f"{report['tune_hist'][-1]:.4f}; eval log-ppl "
          f"{report['metrics']['log_ppl']:.4f}")
    return params, report


# ---------------------------------------------------------------------------
# (b) serve: paged engine vs contiguous engine
# ---------------------------------------------------------------------------

def serve_traffic(vocab: int, seed: int, *, prefix_len: int = 128,
                  prompt_lens=(128, 224, 352, 512)):
    """Prompts that all start with one shared ``prefix_len``-token prefix."""
    rng = np.random.default_rng((seed, 515))
    prefix = rng.integers(0, vocab, prefix_len)
    return [np.concatenate([prefix, rng.integers(0, vocab, p - prefix_len)])
            [None].astype(np.int32) for p in prompt_lens]


def run_engine(engine, prompts, gen: int):
    for p in prompts:
        engine.submit({"tokens": jnp.asarray(p)}, max_new=gen)
    comps = engine.run()
    return {uid: c.tokens.tolist() for uid, c in sorted(comps.items())}


def _first_divergence(got, want):
    for uid in want:
        a, b = got.get(uid, []), want[uid]
        for i, (x, y) in enumerate(zip(a, b)):
            if x != y:
                return uid, i
        if len(a) != len(b):
            return uid, min(len(a), len(b))
    return None


def serve_phase(params, cfg: ModelConfig, rec: Recorder, *, seed: int = 0,
                gen: int = 32, chunk_len: int = 64, block_len: int = 16,
                prefix_len: int = 128, prompt_lens=(128, 224, 352, 512),
                mesh=None, tag: str = "serve"):
    """Greedy tokens of ``PagedServeEngine`` (bucketed chunked admission,
    on ``mesh``) must equal the single-device contiguous
    ``ServeEngine``'s on the same traffic.

    Both engines run in float32 at matmul precision "highest": the
    argmax over 151936 logits of a barely trained model is a near-tie,
    and the bf16 rounding of the Pallas paged attention and the XLA
    contiguous attention differs enough to flip it (seen on a v5e)."""
    cfg = cfg.replace(dtype="float32")
    params = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    prompts = serve_traffic(cfg.vocab_size, seed, prefix_len=prefix_len,
                            prompt_lens=prompt_lens)
    max_len = max(M.decode_capacity(cfg, p.shape[1], gen) for p in prompts)
    kw = dict(n_slots=len(prompts), max_len=max_len, chunk_len=chunk_len)
    with jax.default_matmul_precision("highest"):
        paged = PagedServeEngine(params, cfg, block_len=block_len, mesh=mesh,
                                 **kw)
        got = run_engine(paged, prompts, gen)
        rec.mark(f"{tag} paged", tokens=paged.stats["generated_tokens"],
                 shared_blocks=paged.stats["shared_blocks"],
                 peak_bytes=peak_bytes())
        ref = ServeEngine(params, cfg, **kw)
        want = run_engine(ref, prompts, gen)
        rec.mark(f"{tag} contiguous", tokens=ref.stats["generated_tokens"],
                 peak_bytes=peak_bytes())
    div = _first_divergence(got, want)
    if div is not None:
        uid, i = div
        raise RuntimeError(
            f"{tag}: paged tokens diverge from the contiguous engine at "
            f"request {uid} token {i}: {got[uid][:i + 1]} vs "
            f"{want[uid][:i + 1]}")
    log_line(f"{tag}: {len(got)} requests x {gen} greedy tokens identical "
             f"(paged on {'one device' if mesh is None else dict(mesh.shape)}"
             f" vs contiguous on one device); request 0 starts "
             f"{got[0][:8]}")
    return got


# ---------------------------------------------------------------------------
# --chips 4: expert parallelism across chips
# ---------------------------------------------------------------------------

def train_ep_phase(cfg: ModelConfig, rec: Recorder, *, n_chips: int = 4,
                   steps: int = 3, batch: int = 4, seq: int = 256,
                   seed: int = 0):
    """``launch/train.py`` steps on the (data=1, model=n_chips) host mesh
    (the MoE runs ``moe_a2a``) against the same steps on one chip."""
    kw = dict(steps=steps, batch=batch, seq=seq, lr=1e-3, seed=seed,
              moment_policy="bf16", log=log_line)
    _, ep = train_steps(cfg, make_host_mesh(n_chips), **kw)
    rec.mark(f"train a2a {n_chips} chips", peak_bytes=peak_bytes())
    _, one = train_steps(cfg, make_host_mesh(1), **kw)
    rec.mark("train 1 chip", peak_bytes=peak_bytes())
    _check_finite("a2a training", ep)
    _check_finite("one-chip training", one)
    diff = max(abs(a - b) for a, b in zip(ep, one))
    log_line(f"per-step loss, {n_chips} chips: {ep}")
    log_line(f"per-step loss, 1 chip:  {one}")
    if diff > LOSS_TOL:
        raise RuntimeError(f"a2a losses differ from one chip by {diff} "
                           f"> {LOSS_TOL}")
    log_line(f"a2a vs one chip: max |loss diff| {diff:.3e} <= {LOSS_TOL}")
    return ep, one


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="4: run only the expert-parallel training and "
                         "sharded decode, each against one chip")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} "
              f"devices", file=sys.stderr)
        return 2

    cache = enable_compile_cache()
    ir_dir = os.path.join(ROOT, ".chip_smoke_ir")
    shutil.rmtree(ir_dir, ignore_errors=True)
    jax.config.update("jax_dump_ir_to", ir_dir)
    jax.config.update("jax_include_debug_info_in_dumps", False)
    handler = logging.StreamHandler(sys.stdout)
    handler.setFormatter(logging.Formatter("  log: %(message)s"))
    repro_log = logging.getLogger("repro")
    repro_log.setLevel(logging.INFO)
    repro_log.addHandler(handler)

    log_line(f"device: {dev.platform} {dev.device_kind} x{len(devices)} "
             f"(jax {jax.__version__}); compile cache {cache}")
    moe, device_cfgs, moe_cuts, device_cuts = smoke_configs()
    # the four-chip checks run no device model
    for c in moe_cuts + (device_cuts if args.chips == 1 else []):
        log_line(f"reduced: {c}")
    rec = Recorder(ir_dir)
    try:
        if args.chips == 4:
            # XLA cannot partition a Pallas kernel called outside
            # shard_map (flash attention, the KD loss, paged attention
            # take mesh-sharded operands here), so both checks run the
            # XLA paths of the same MoE on both sides
            ep_cfg = moe.replace(use_pallas=False)
            log_line("phase: expert-parallel training, use_pallas=False "
                     "(capacity_factor 2.0 so neither run drops tokens; "
                     "bf16 AdamW moments)")
            train_ep_phase(ep_cfg.replace(capacity_factor=2.0), rec,
                           seed=args.seed)
            log_line("phase: sharded decode on make_decode_mesh(4), "
                     "use_pallas=False, float32")
            params = M.init_params(jax.random.PRNGKey(args.seed), ep_cfg)
            serve_phase(params, ep_cfg, rec, seed=args.seed,
                        mesh=make_decode_mesh(4), tag="serve sharded")
        else:
            log_line("phase (a): train — run_deepfusion")
            params, _ = train_phase(moe, device_cfgs, rec, seed=args.seed)
            log_line("phase (b): serve — paged vs contiguous, float32")
            serve_phase(params, moe, rec, seed=args.seed)
            missing = {}
            for part, want in EXPECTED_KERNELS.items():
                lack = want - set(rec.parts.get(part, {}).get("kernels", ()))
                if lack:
                    missing[part] = sorted(lack)
            if missing:
                raise RuntimeError(f"kernels missing from the chip programs: "
                                   f"{missing}")
    finally:
        rec.close()
        shutil.rmtree(ir_dir, ignore_errors=True)
    for part, r in rec.parts.items():
        ks = ", ".join(f"{k} x{n}" for k, n in sorted(r["kernels"].items()))
        log_line(f"summary [{part}]: wall {r['wall_s']:.1f}s compile "
                 f"{r['compile_s']:.1f}s; kernels: {ks or 'none'}")
    log_line(f"peak_bytes_in_use: {peak_bytes()}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
