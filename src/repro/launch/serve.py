"""Serving launcher: continuous-batching engine over every arch family.

Thin client of ``repro.serve.ServeEngine`` — prefill grafting, the
scanned decode loop and slot admission all live in the engine / model
layer.  All six families run, including encdec (whisper: stub audio
frames feed the encoder, the decoder prompt is served like any other).

  PYTHONPATH=src python -m repro.launch.serve --arch mamba2-1.3b \
      --variant reduced --requests 4 --prompt-len 32 --gen 16
  PYTHONPATH=src python -m repro.launch.serve --arch whisper-small \
      --variant reduced --requests 3 --mixed
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_decode_mesh, make_host_mesh
from repro.models import model as M
from repro.models.layers import paged_read_path
from repro.serve import (Greedy, PagedServeEngine, ServeEngine, Temperature,
                         TopK)


def mixed_lengths(n: int, prompt_len: int, gen: int):
    """Demo traffic: request i gets a shorter prompt + generation."""
    return [(max(4, prompt_len - 4 * i), max(2, gen - 3 * i))
            for i in range(n)]


def prompt_batch(cfg, rng, prompt_len: int):
    """A leading-dim-1 prefill batch for any arch family."""
    toks = rng.integers(0, cfg.vocab_size, (1, prompt_len))
    batch = {"tokens": jnp.asarray(toks, jnp.int32)}
    dt = jnp.dtype(cfg.dtype)
    if cfg.arch_type == "vlm":
        batch["patches"] = jnp.asarray(
            rng.normal(size=(1, cfg.frontend_tokens, cfg.d_model)) * 0.05, dt)
    if cfg.arch_type == "encdec":
        batch["frames"] = jnp.asarray(
            rng.normal(size=(1, cfg.frontend_tokens, cfg.d_model)) * 0.05, dt)
    return batch


def pick_sampler(args):
    if args.top_k:
        return TopK(args.top_k, args.temperature or 1.0)
    if args.temperature:
        return Temperature(args.temperature)
    return Greedy()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--variant", default="reduced")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--seg-len", type=int, default=8)
    ap.add_argument("--vocab", type=int, default=512)
    ap.add_argument("--mixed", action="store_true",
                    help="vary prompt/gen length per request")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--paged", action="store_true",
                    help="serve through the block-paged KV engine")
    ap.add_argument("--block-len", type=int, default=8,
                    help="paged engine: tokens per KV block")
    ap.add_argument("--blocks", type=int, default=0,
                    help="paged engine: pool size (0 = worst-case default)")
    ap.add_argument("--bucket", action="store_true",
                    help="bucketed chunked-prefill admission (compiles "
                         "O(#buckets) executables, not one per length)")
    ap.add_argument("--chunk-len", type=int, default=4,
                    help="bucketed admission: tokens per prefill chunk")
    ap.add_argument("--buckets", default="",
                    help="comma-separated bucket ladder (default: "
                         "powers-of-two chunk multiples)")
    ap.add_argument("--eager-blocks", action="store_true",
                    help="paged engine: reserve a request's worst-case "
                         "blocks at admission instead of lazily")
    ap.add_argument("--check-unbucketed", action="store_true",
                    help="replay the same traffic through an unbucketed "
                         "engine and fail unless completions match")
    ap.add_argument("--sharded", action="store_true",
                    help="serve on the decode mesh (data x model over every "
                         "visible device) instead of the flat host mesh")
    ap.add_argument("--overlap-a2a", action="store_true",
                    help="MoE decode: overlap the EP all-to-all with "
                         "attention compute (batch-level split)")
    ap.add_argument("--check-unsharded", action="store_true",
                    help="replay the same traffic single-device (mesh=None, "
                         "overlap off) and fail unless completions match")
    ap.add_argument("--speculate", action="store_true",
                    help="self-speculative MTP decode: draft + verify "
                         "n-draft tokens inside each compiled scan step "
                         "(needs an arch with an MTP head, cfg.n_mtp > 0)")
    ap.add_argument("--n-draft", type=int, default=3,
                    help="speculative decode: draft tokens per step")
    ap.add_argument("--check-unspeculated", action="store_true",
                    help="replay the same traffic without speculation and "
                         "fail unless completions match")
    ap.add_argument("--kv-dtype", default="",
                    choices=["", "fp32", "bf16", "fp8", "int8"],
                    help="KV-cache storage policy: int8/fp8 quantize pool "
                         "rows with per-position scales (repro.models.quant)")
    ap.add_argument("--check-unquantized", action="store_true",
                    help="replay the same traffic at full precision and "
                         "fail unless greedy completions match")
    args = ap.parse_args()
    if args.buckets and not args.bucket:
        ap.error("--buckets requires --bucket")
    if args.check_unbucketed and not args.bucket:
        ap.error("--check-unbucketed requires --bucket")
    if args.check_unsharded and not args.sharded:
        ap.error("--check-unsharded requires --sharded")
    if args.check_unspeculated and not args.speculate:
        ap.error("--check-unspeculated requires --speculate")
    if args.check_unquantized and args.kv_dtype not in ("int8", "fp8"):
        ap.error("--check-unquantized requires a quantized --kv-dtype")
    enable_compile_cache()

    cfg = get_config(args.arch, variant=args.variant)
    if args.variant == "reduced":
        cfg = cfg.replace(vocab_size=args.vocab)
    if args.overlap_a2a:
        cfg = cfg.replace(overlap_a2a=True)
    mesh = make_decode_mesh() if args.sharded else make_host_mesh()
    rng = np.random.default_rng(0)

    P, G = args.prompt_len, args.gen
    if args.mixed:
        lengths = mixed_lengths(args.requests, P, G)
    else:
        lengths = [(P, G)] * args.requests
    # caches sized exactly: prompt + max_new (+ VLM patch offset), no +1
    max_len = max(M.decode_capacity(cfg, p, g) for p, g in lengths)

    params = M.init_params(jax.random.PRNGKey(0), cfg)
    bucket_kw = {}
    if args.bucket:
        bucket_kw["chunk_len"] = args.chunk_len
        if args.buckets:
            bucket_kw["buckets"] = [int(b) for b in args.buckets.split(",")]
    if args.speculate:
        bucket_kw["speculate"] = args.n_draft  # rides every engine below
    if args.kv_dtype:
        bucket_kw["kv_dtype"] = args.kv_dtype
    with mesh:
        if args.paged:
            engine = PagedServeEngine(
                params, cfg, n_slots=args.slots, max_len=max_len,
                sampler=pick_sampler(args), seg_len=args.seg_len, mesh=mesh,
                block_len=args.block_len,
                n_blocks=args.blocks or None,
                lazy=not args.eager_blocks, **bucket_kw)
        else:
            engine = ServeEngine(params, cfg, n_slots=args.slots,
                                 max_len=max_len, sampler=pick_sampler(args),
                                 seg_len=args.seg_len, mesh=mesh, **bucket_kw)
        batches = [prompt_batch(cfg, rng, p) for p, _ in lengths]
        for b, (_, g) in zip(batches, lengths):
            engine.submit(b, max_new=g)
        t0 = time.time()
        comps = engine.run()
        dt = time.time() - t0
    n_tok = engine.stats["generated_tokens"]
    util = (engine.stats["live_slot_steps"] / max(engine.stats["slot_steps"], 1))
    print(f"{args.arch}: {len(comps)} requests, {n_tok} tokens in {dt:.2f}s "
          f"({n_tok / dt:.1f} tok/s, {engine.stats['segments']} segments, "
          f"slot util {util:.0%})")
    if args.bucket:
        print(f"bucketed: chunk_len={engine.chunk_len} "
              f"ladder={list(engine.buckets)} "
              f"compiles={engine.compiles_built}")
    if args.paged:
        print(f"paged: block_len={engine.block_len} pool={engine.n_blocks} "
              f"peak_blocks={engine.stats['peak_live_blocks']} "
              f"shared={engine.stats['shared_blocks']} "
              f"lazy_claimed={engine.stats['lazy_claimed_blocks']} "
              f"preemptions={engine.stats['preemptions']} "
              f"(free after drain: {engine.alloc.n_free}, "
              f"read path: {paged_read_path(cfg, 1)}, "
              f"allocator shards: {engine.alloc.n_shards})")
    if args.kv_dtype:
        cache_bytes = (M.paged_cache_nbytes(cfg, args.slots, engine.n_blocks,
                                            engine.block_len,
                                            policy=engine.policy)
                       if args.paged else
                       M.cache_nbytes(cfg, args.slots, max_len,
                                      policy=engine.policy))
        print(f"kv-dtype: {args.kv_dtype} cache_bytes={cache_bytes}")
    if args.sharded:
        print(f"sharded: mesh={dict(mesh.shape)} "
              f"overlap_a2a={cfg.overlap_a2a}")
    first = comps[min(comps)]
    print("sample:", first.tokens[:16])
    if args.speculate:
        print(f"speculative: n_draft={args.n_draft} "
              f"acceptance={engine.spec_acceptance():.1%} "
              f"({engine.stats['spec_extra_tokens']} extra tokens over "
              f"{engine.stats['spec_steps']} live steps)")
    if args.check_unbucketed:
        with mesh:
            ref = ServeEngine(params, cfg, n_slots=args.slots,
                              max_len=max_len, sampler=pick_sampler(args),
                              seg_len=args.seg_len, mesh=mesh)
            for b, (_, g) in zip(batches, lengths):
                ref.submit(b, max_new=g)
            ref_comps = ref.run()
        got = {u: c.tokens.tolist() for u, c in comps.items()}
        want = {u: c.tokens.tolist() for u, c in ref_comps.items()}
        if got != want:
            raise SystemExit(
                f"bucketed completions diverged from unbucketed: "
                f"{got} != {want}")
        print(f"check-unbucketed: completions match "
              f"({ref.compiles_built} reference compiles vs "
              f"{engine.compiles_built} bucketed)")
    if args.check_unsharded:
        ref_cfg = cfg.replace(overlap_a2a=False)
        if args.paged:
            ref = PagedServeEngine(
                params, ref_cfg, n_slots=args.slots, max_len=max_len,
                sampler=pick_sampler(args), seg_len=args.seg_len, mesh=None,
                block_len=args.block_len, n_blocks=args.blocks or None,
                lazy=not args.eager_blocks, **bucket_kw)
        else:
            ref = ServeEngine(params, ref_cfg, n_slots=args.slots,
                              max_len=max_len, sampler=pick_sampler(args),
                              seg_len=args.seg_len, mesh=None, **bucket_kw)
        for b, (_, g) in zip(batches, lengths):
            ref.submit(b, max_new=g)
        ref_comps = ref.run()
        got = {u: c.tokens.tolist() for u, c in comps.items()}
        want = {u: c.tokens.tolist() for u, c in ref_comps.items()}
        if got != want:
            raise SystemExit(
                f"sharded completions diverged from single-device: "
                f"{got} != {want}")
        print("check-unsharded: completions match")
    if args.check_unspeculated:
        plain_kw = {k: v for k, v in bucket_kw.items() if k != "speculate"}
        with mesh:
            if args.paged:
                ref = PagedServeEngine(
                    params, cfg, n_slots=args.slots, max_len=max_len,
                    sampler=pick_sampler(args), seg_len=args.seg_len,
                    mesh=mesh, block_len=args.block_len,
                    n_blocks=args.blocks or None,
                    lazy=not args.eager_blocks, **plain_kw)
            else:
                ref = ServeEngine(params, cfg, n_slots=args.slots,
                                  max_len=max_len,
                                  sampler=pick_sampler(args),
                                  seg_len=args.seg_len, mesh=mesh,
                                  **plain_kw)
            for b, (_, g) in zip(batches, lengths):
                ref.submit(b, max_new=g)
            t0 = time.time()
            ref_comps = ref.run()
            ref_dt = time.time() - t0
        got = {u: c.tokens.tolist() for u, c in comps.items()}
        want = {u: c.tokens.tolist() for u, c in ref_comps.items()}
        if got != want:
            raise SystemExit(
                f"speculative completions diverged from plain decode: "
                f"{got} != {want}")
        print(f"check-unspeculated: completions match "
              f"({engine.stats['segments']} speculative segments vs "
              f"{ref.stats['segments']} plain, replay {ref_dt:.2f}s)")
    if args.check_unquantized:
        fp_kw = {k: v for k, v in bucket_kw.items() if k != "kv_dtype"}
        with mesh:
            if args.paged:
                ref = PagedServeEngine(
                    params, cfg, n_slots=args.slots, max_len=max_len,
                    sampler=pick_sampler(args), seg_len=args.seg_len,
                    mesh=mesh, block_len=args.block_len,
                    n_blocks=args.blocks or None,
                    lazy=not args.eager_blocks, **fp_kw)
            else:
                ref = ServeEngine(params, cfg, n_slots=args.slots,
                                  max_len=max_len, sampler=pick_sampler(args),
                                  seg_len=args.seg_len, mesh=mesh, **fp_kw)
            for b, (_, g) in zip(batches, lengths):
                ref.submit(b, max_new=g)
            ref_comps = ref.run()
        got = {u: c.tokens.tolist() for u, c in comps.items()}
        want = {u: c.tokens.tolist() for u, c in ref_comps.items()}
        if got != want:
            raise SystemExit(
                f"{args.kv_dtype} completions diverged from full "
                f"precision: {got} != {want}")
        print(f"check-unquantized: {args.kv_dtype} completions match "
              f"full precision")


if __name__ == "__main__":
    main()
