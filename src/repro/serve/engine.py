"""Slot-based continuous-batching generation engine.

The engine serves a queue of variable-length requests through a fixed
set of ``n_slots`` batch rows:

  admit    : prefill a queued request at B=1, graft its cache into a
             free slot (``prefill_into_cache`` + a per-slot scatter),
             sample emission #1 from the prefill logits.
  segment  : ONE compiled ``lax.scan`` of ``seg_len`` decode steps over
             the whole batch (``models.model.generate``), per-slot
             position / remaining-length / EOS state carried through the
             scan.  Finished slots keep running as masked garbage until
             the segment ends — shapes never change, nothing recompiles.
  between  : finished slots are freed and refilled from the queue, so
             mixed-length traffic keeps the batch full instead of
             padding every request to the longest one.

Two cache layouts share that lifecycle:

``ServeEngine`` (contiguous) owns one ``(n_slots, max_len)`` decode
cache — engine capacity is ``n_slots * max_len`` rows no matter how
short requests are.  ``PagedServeEngine`` owns an ``(n_blocks,
block_len)`` block pool per attention leaf plus per-slot block tables
(``repro.serve.paged``): a request holds exactly the blocks its own
capacity spans, identical prompt prefixes are pooled once (refcounted,
copy-on-write resolved at admission), and slot count is bounded by live
tokens rather than ``n_slots * max_len``.

Slot independence: attention/SSM state and MoE routing never mix batch
rows — the decode scan threads a per-row liveness mask (``~done``) into
``decode_step``, so freed garbage lanes are zeroed out of router
probabilities AND excluded from expert-capacity ranking on every MoE
path, including the multi-device ``moe_a2a`` one (a freed slot can
never crowd a live token out of an expert; see
tests/test_serve_sharded.py).  A request's tokens are therefore
identical to a solo run with the same per-request PRNG key.

Sharded serving: pass ``mesh=`` and the engine lays its decode cache
(or block pools) out with ``NamedSharding`` per ``sharding.rules`` —
slots over the data axes, pool/feature dims over "model" — and every
compiled admit/segment executable runs sharded.  A 1-device mesh is
bit-identical to ``mesh=None``.
"""
from __future__ import annotations

import dataclasses
import time
from collections import OrderedDict, deque
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import model as M
from repro.models import quant
from repro.models.config import ModelConfig
from repro.serve import bucketing as bk
from repro.serve import paged as pg
from repro.serve.sampling import Greedy


@dataclasses.dataclass
class Request:
    """One generation request.  ``batch`` is a leading-dim-1 prefill
    batch (``tokens`` plus ``patches``/``frames`` for vlm/encdec);
    ``max_new`` counts ALL generated tokens, including the one sampled
    from the prefill logits."""
    uid: int
    batch: Dict[str, Any]
    max_new: int
    key: Optional[Any] = None  # per-request PRNG key (seeded from uid if None)
    # memoised prefix-block content keys (paged engine): hashing the
    # prompt/modality bytes is done once, not per blocked admission retry
    plan_keys: Optional[List] = None

    @property
    def prompt_len(self) -> int:
        return self.batch["tokens"].shape[1]


@dataclasses.dataclass
class Completion:
    uid: int
    prompt_len: int
    tokens: np.ndarray     # (n_generated,) — includes the EOS token if hit
    n_segments: int        # decode segments this request rode through


class CompiledLRU:
    """Bounded per-shape executable cache.

    Under open-world traffic every distinct prompt length compiles (and
    permanently pins) a fresh prefill/admit executable if cached in an
    unbounded ``lru_cache`` — evicting the per-length jitted callable
    here drops its executables with it.  ``builds`` counts every build
    (including rebuilds after eviction): the compile-thrash count.
    """

    def __init__(self, build: Callable[[Any], Callable], maxsize: int = 32):
        self._build, self._maxsize = build, max(maxsize, 1)
        self._cache: OrderedDict = OrderedDict()
        self.builds = 0

    def __call__(self, key):
        fn = self._cache.get(key)
        if fn is None:
            fn = self._build(key)
            self.builds += 1
            self._cache[key] = fn
            if len(self._cache) > self._maxsize:
                self._cache.popitem(last=False)
        else:
            self._cache.move_to_end(key)
        return fn

    def __len__(self) -> int:
        return len(self._cache)


def _scatter_slot_row(cache, sub, slot, bat, seq=None):
    """Write a B=1 cache subtree back into row ``slot`` of the batched
    cache along each leaf's batch axis (``bat`` from
    ``decode_cache_batch_axes``).  Leaves with a sequence axis in
    ``seq`` (paged pools) pass through unchanged — they were updated in
    place through the block tables."""
    if seq is None:
        seq = jax.tree.map(lambda _: -1, bat)

    def put(dst, src, bax, sax):
        if sax >= 0:
            return src
        idx = [slice(None)] * dst.ndim
        idx[bax] = slot
        return dst.at[tuple(idx)].set(
            jnp.take(src, 0, axis=bax).astype(dst.dtype))

    return jax.tree.map(put, cache, sub, bat, seq)


class ServeEngine:
    """Continuous-batching engine over a fixed ``(n_slots, max_len)``
    decode cache.  ``submit()`` requests, then ``run()`` (or ``step()``
    segment-by-segment for external admission control); drain finished
    requests with ``pop_completions()`` under sustained traffic.

    With ``chunk_len`` set, admission switches to **bucketed chunked
    prefill**: the padded input length is rounded up a bucket ladder
    (``buckets``, default powers-of-two chunk multiples) and the prompt
    runs through the shared decode body in ``chunk_len``-token chunks
    directly into the slot's cache row — no separate B=1 prefill graft,
    and the admission executable is keyed on the BUCKET, so open-world
    traffic compiles O(#buckets) executables instead of one per
    distinct prompt length.  Output is token-identical to the
    unbucketed engine (greedy ties aside; chunked and one-shot prefill
    agree to float epsilon, not bitwise).
    """

    def __init__(self, params, cfg: ModelConfig, *, n_slots: int = 4,
                 max_len: int = 128, sampler=None, eos_id: Optional[int] = None,
                 seg_len: int = 8, mesh=None, seed: int = 0,
                 history_limit: int = 4096, compile_cache_size: int = 32,
                 chunk_len: Optional[int] = None, buckets=None,
                 speculate: int = 0, kv_dtype: str = ""):
        cfg.validate()
        if cfg.is_moe and not cfg.moe_dropless:
            # capacity drops are a training-time tradeoff; serving must
            # keep single-device semantics on any mesh, so expert
            # buffers are sized worst-case (no token ever dropped)
            cfg = cfg.replace(moe_dropless=True)
        self.speculate = int(speculate)
        if self.speculate and not (cfg.n_mtp and "mtp" in params):
            raise ValueError(
                "speculate requires an MTP head: cfg.n_mtp > 0 with "
                "params['mtp'] (dense/moe/vlm families)")
        self.params, self.cfg = params, cfg
        # cache storage policy: "" keeps the param dtype; int8/fp8 store
        # KV quantized with per-position scale leaves (repro.models.quant)
        self.kv_dtype = kv_dtype
        self.policy = quant.CachePolicy(kv_dtype)
        self.n_slots, self.max_len, self.seg_len = n_slots, max_len, seg_len
        self.sampler = sampler if sampler is not None else Greedy()
        self.eos_id, self.mesh = eos_id, mesh
        self._base_key = jax.random.PRNGKey(seed)
        self.chunk_len = chunk_len
        if chunk_len is not None:
            ladder = (bk.bucket_ladder(chunk_len, max_len)
                      if buckets is None else buckets)
            self.buckets = bk.validate_ladder(ladder, chunk_len)
        else:
            if buckets is not None:
                raise ValueError("buckets requires chunk_len")
            self.buckets = None
        # bounded per-shape executable caches (see CompiledLRU): keyed on
        # prompt length (unbucketed) or bucket rung (chunked admission)
        self._prefill_exec = CompiledLRU(self._build_prefill,
                                         compile_cache_size)
        self._admit_exec = CompiledLRU(self._build_admit, compile_cache_size)
        self._init_cache()
        # per-slot host state
        self.tok = np.zeros((n_slots,), np.int32)
        self.pos = np.zeros((n_slots,), np.int32)
        self.rem = np.zeros((n_slots,), np.int32)
        # speculative-decode draft seed: final-normed hidden of the
        # position that emitted the slot's pending token.  Zeros at
        # admission — a cold first draft simply gets rejected.
        self.h_spec = np.zeros((n_slots, cfg.d_model), jnp.dtype(cfg.dtype))
        self.keys = np.array(jax.random.split(self._base_key, n_slots))
        self.slot_uid = np.full((n_slots,), -1, np.int64)
        self._slot_seq = np.zeros((n_slots,), np.int64)  # admission order
        self._admit_seq = 0
        self._live_req: Dict[int, Request] = {}  # uid -> Request while live
        self.queue: deque = deque()
        self._pending: set = set()  # queued uids — O(1) reuse check
        self.completions: Dict[int, Completion] = {}
        self.history: deque = deque(maxlen=history_limit)  # (seg, slot, uid)
        self.segment_idx = 0
        self.stats = {"generated_tokens": 0, "segments": 0, "prefills": 0,
                      "slot_steps": 0, "live_slot_steps": 0,
                      "spec_steps": 0, "spec_extra_tokens": 0,
                      "peak_live_requests": 0}
        self._out: Dict[int, list] = {}
        self._plen: Dict[int, int] = {}
        self._nseg: Dict[int, int] = {}
        self._uid_auto = 0

    @property
    def compiles_built(self) -> int:
        """Total prefill/admit executables built so far (rebuilds after
        LRU eviction included) — O(#buckets) under chunked admission,
        O(#distinct prompt lengths) without."""
        return self._prefill_exec.builds + self._admit_exec.builds

    # -- cache layout hooks (overridden by PagedServeEngine) ---------------

    def _init_cache(self) -> None:
        self.cache = M.init_decode_cache(self.cfg, self.n_slots, self.max_len,
                                         mesh=self.mesh, policy=self.policy)
        self._cache_shardings = self._shardings_of(self.cache)

    def _shardings_of(self, cache):
        """Per-leaf NamedShardings of the engine cache (None when
        single-device).  Captured once at init: cache donation makes
        every compiled segment/admit preserve this placement, and the
        admit builders re-constrain their outputs to it as insurance."""
        if self.mesh is None or self.mesh.size == 1:
            return None
        return jax.tree.map(lambda x: x.sharding, cache)

    def _constrain_cache(self, cache):
        if self._cache_shardings is None:
            return cache
        return jax.tree.map(jax.lax.with_sharding_constraint, cache,
                            self._cache_shardings)

    def _build_prefill(self, P: int):
        cfg, mesh, spec = self.cfg, self.mesh, bool(self.speculate)
        return jax.jit(lambda p, b: M.prefill(p, cfg, b, mesh=mesh,
                                              return_hidden=spec))

    def _build_admit(self, key):
        """Jitted admission, one dispatch, batched cache donated.

        Unbucketed (``key`` = prompt length): graft a B=1 prefill cache
        and scatter it into row ``slot`` of the engine's batched cache
        (batch axis per leaf from ``decode_cache_batch_axes``).

        Chunked (``key`` = bucket rung): slice the slot's B=1 cache
        view, run ``prefill_chunked`` through it, scatter the view back
        and return the last real token's logits — prompt length and
        slot are runtime operands, so every prompt in the bucket reuses
        this one executable."""
        if self.chunk_len is not None:
            return self._build_admit_chunked(key)
        cfg, max_len = self.cfg, self.max_len
        axes = M.decode_cache_batch_axes(cfg, policy=self.policy)

        def admit(cache, pc, slot):
            sub = M.prefill_into_cache(
                cfg, M.init_decode_cache(cfg, 1, max_len), pc)
            # quantized engines graft full-precision, then quantize the
            # whole slot row to the cache's policy (adds scale leaves)
            sub = M.match_cache_policy(cache, sub)
            return self._constrain_cache(_scatter_slot_row(cache, sub, slot,
                                                           axes))

        return jax.jit(admit, donate_argnums=(0,))

    def _build_admit_chunked(self, rung: int):
        cfg, mesh, C = self.cfg, self.mesh, self.chunk_len
        axes = M.decode_cache_batch_axes(cfg, policy=self.policy)

        def admit(params, cache, batch, prompt_len, slot):
            s1 = jnp.reshape(slot, (1,))
            sub = jax.tree.map(
                lambda leaf, ax: jnp.take(leaf, s1, axis=ax), cache, axes)
            logits, sub = M.prefill_chunked(params, cfg, sub, batch,
                                            prompt_len, chunk_len=C,
                                            mesh=mesh)
            cache = self._constrain_cache(
                _scatter_slot_row(cache, sub, slot, axes))
            return logits, cache

        return jax.jit(admit, donate_argnums=(1,))

    # -- request intake ----------------------------------------------------

    def submit(self, batch, *, max_new: int, uid: Optional[int] = None,
               key=None) -> int:
        if uid is None:
            uid = self._uid_auto
            self._uid_auto += 1
        else:
            self._uid_auto = max(self._uid_auto, uid + 1)
        if uid in self.completions or uid in self._out or uid in self._pending:
            raise ValueError(f"request uid {uid} already in use")
        bad = [k for k, v in batch.items() if v.shape[0] != 1]
        if bad:
            raise ValueError(
                f"request {uid}: batch entries {bad} must have leading dim 1 "
                f"(one request per submit)")
        self._validate_capacity(uid, batch["tokens"].shape[1], max_new)
        if max_new < 1:
            raise ValueError(f"request {uid}: max_new must be >= 1")
        self.queue.append(Request(uid, batch, max_new, key))
        self._pending.add(uid)
        return uid

    def _validate_capacity(self, uid: int, P: int, max_new: int) -> None:
        need = M.decode_capacity(self.cfg, P, max_new)
        if need > self.max_len:
            raise ValueError(
                f"request {uid}: prompt {P} + max_new {max_new} needs cache "
                f"capacity {need} > engine max_len {self.max_len}")

    @property
    def idle(self) -> bool:
        return not self.queue and not (self.slot_uid >= 0).any()

    def pop_completions(self) -> Dict[int, Completion]:
        """Drain finished requests — the bound on ``completions`` growth
        under sustained traffic (their uids become reusable)."""
        out, self.completions = self.completions, {}
        return out

    # -- admission ---------------------------------------------------------

    def _finish(self, uid: int) -> None:
        self._live_req.pop(uid, None)
        self.completions[uid] = Completion(
            uid, self._plen.pop(uid),
            np.asarray(self._out.pop(uid), np.int32), self._nseg.pop(uid))

    def _bucket_rung(self, P: int) -> int:
        """Bucket for a P-token prompt: the padded INPUT length
        (modality frontend + tokens) rounded up the ladder."""
        return bk.bucket_for(M.decode_pos0(self.cfg, P), self.buckets,
                             self.chunk_len)

    def _padded_batch(self, req: Request, rung: int):
        """The request's batch with tokens right-padded so the full
        input sequence is exactly ``rung`` long (pad values are masked
        out of cache/state by the chunked prefill contract)."""
        T_pad = rung - M.decode_offset(self.cfg)
        toks = np.zeros((1, T_pad), np.int32)
        toks[:, :req.prompt_len] = np.asarray(req.batch["tokens"])
        batch = dict(req.batch)
        batch["tokens"] = jnp.asarray(toks)
        return batch

    def _plan(self, req: Request):
        """Admission plan (bucket rung; paged adds block keys/counts).
        None = nothing to plan (unbucketed contiguous admission)."""
        if self.chunk_len is None:
            return None
        return {"rung": self._bucket_rung(req.prompt_len)}

    def _fits(self, plan) -> bool:
        """Can the planned request be placed right now?"""
        return True

    def _place(self, slot: int, req: Request, pc, plan) -> None:
        self.cache = self._admit_exec(req.prompt_len)(self.cache, pc, slot)

    def _admit_chunked_into(self, slot: int, req: Request, plan):
        """Run the bucketed chunked prefill straight into ``slot``'s
        cache row; returns the last real token's logits (1, V)."""
        rung = plan["rung"]
        logits, self.cache = self._admit_exec(rung)(
            self.params, self.cache, self._padded_batch(req, rung),
            jnp.int32(req.prompt_len), jnp.int32(slot))
        return logits

    def _rollback_place(self, slot: int, req: Request) -> None:
        """Undo a chunked placement whose request finished at prefill
        (max_new == 1 / instant EOS): the slot was never marked live, so
        only layout resources (paged blocks) need returning."""

    def _release_slot(self, slot: int) -> None:
        self.slot_uid[slot] = -1
        # EOS can finish a slot with budget left: zero it so the freed
        # lane runs masked (done = rem<=0) until re-admitted
        self.rem[slot] = 0
        self.h_spec[slot] = 0

    def _admit(self) -> None:
        free = [s for s in range(self.n_slots) if self.slot_uid[s] < 0]
        while free and self.queue:
            req = self.queue[0]
            plan = self._plan(req)
            if not self._fits(plan):
                break  # blocked on pool space: keep arrival order
            self.queue.popleft()
            self._pending.discard(req.uid)
            key = req.key if req.key is not None else \
                jax.random.fold_in(self._base_key, req.uid)
            key, k0 = jax.random.split(key)
            if self.chunk_len is None:
                # unbucketed: slotless B=1 prefill, graft deferred so a
                # request finishing at prefill never touches the cache
                slot = free[0]
                logits, pc = self._prefill_exec(req.prompt_len)(self.params,
                                                                req.batch)
                if self.speculate:
                    logits, h0 = logits  # return_hidden packs (logits, h)
            else:
                # bucketed: the chunked prefill IS the placement — it
                # writes through the slot's cache row / block tables
                slot = free[0]
                logits = self._admit_chunked_into(slot, req, plan)
            e0 = int(np.asarray(self.sampler(k0[None], logits))[0])
            self._out[req.uid] = [e0]
            self._plen[req.uid] = req.prompt_len
            self._nseg[req.uid] = 0
            self.stats["prefills"] += 1
            self.stats["generated_tokens"] += 1
            if req.max_new <= 1 or (self.eos_id is not None
                                    and e0 == self.eos_id):
                self._finish(req.uid)  # done at prefill: no slot consumed
                if self.chunk_len is not None:
                    self._rollback_place(slot, req)
                continue
            free.pop(0)
            if self.chunk_len is None:
                self._place(slot, req, pc, plan)
            self.slot_uid[slot] = req.uid
            self._slot_seq[slot] = self._admit_seq
            self._admit_seq += 1
            self._live_req[req.uid] = req
            self.tok[slot] = e0
            self.pos[slot] = M.decode_pos0(self.cfg, req.prompt_len)
            self.rem[slot] = req.max_new - 1
            if self.speculate and self.chunk_len is None:
                # seed the draft chain with the prefill's last hidden —
                # the hidden of the position that emitted e0 — so the
                # slot's first step drafts hot.  Purely a speed win:
                # draft quality never changes accepted tokens.  Chunked
                # admission stays cold (first drafts simply rejected).
                self.h_spec[slot] = np.asarray(h0[0])
            else:
                self.h_spec[slot] = 0
            self.keys[slot] = np.asarray(key)
        self.stats["peak_live_requests"] = max(
            self.stats["peak_live_requests"], int((self.slot_uid >= 0).sum()))

    # -- scanned decode segment --------------------------------------------

    def _spec_kw(self) -> dict:
        if not self.speculate:
            return {}
        return {"speculate": self.speculate,
                "spec_h": jnp.asarray(self.h_spec)}

    def spec_acceptance(self) -> float:
        """Fraction of the k draft lanes per live step that yielded an
        accepted token (0.0 when not speculating or nothing ran)."""
        denom = self.stats["spec_steps"] * self.speculate
        return self.stats["spec_extra_tokens"] / denom if denom else 0.0

    def _run_segment(self):
        return M.generate(self.params, self.cfg, self.cache,
                          jnp.asarray(self.tok), jnp.asarray(self.pos),
                          steps=self.seg_len, sampler=self.sampler,
                          rng=jnp.asarray(self.keys), eos_id=self.eos_id,
                          remaining=jnp.asarray(self.rem), mesh=self.mesh,
                          **self._spec_kw())

    def _segment(self) -> None:
        res = self._run_segment()
        self.cache = res["cache"]
        if self._cache_shardings is not None:
            # the scanned segment's output shardings are the compiler's
            # choice; re-pin the engine layout (no-op when unchanged)
            self.cache = jax.tree.map(jax.device_put, self.cache,
                                      self._cache_shardings)
        toks, valid = np.asarray(res["tokens"]), np.asarray(res["valid"])
        done = np.asarray(res["done"])
        # writable copies — _admit() mutates these per slot
        self.tok = np.array(res["next_tok"])
        self.pos = np.array(res["pos"])
        self.rem = np.array(res["remaining"])
        self.keys = np.array(res["rng"])
        if self.speculate:
            self.h_spec = np.array(res["h_spec"])
            # a live slot always emits at column i*(k+1) of step i, so
            # those columns count the slot's live steps; every further
            # True column is a token the draft+verify chain got for free
            first = valid[:, ::self.speculate + 1]
            self.stats["spec_steps"] += int(first.sum())
            self.stats["spec_extra_tokens"] += int(valid.sum() - first.sum())
        for s in range(self.n_slots):
            uid = int(self.slot_uid[s])
            if uid < 0:
                continue
            self.history.append((self.segment_idx, s, uid))
            new = toks[s][valid[s]].tolist()
            self._out[uid].extend(new)
            self._nseg[uid] += 1
            self.stats["generated_tokens"] += len(new)
            self.stats["live_slot_steps"] += len(new)
            if done[s]:
                self._finish(uid)
                self._release_slot(s)
        # capacity per segment is seg_len emissions per slot, times the
        # chunk width when speculating (each step can emit up to k+1)
        self.stats["slot_steps"] += (self.n_slots * self.seg_len
                                     * (self.speculate + 1))
        self.stats["segments"] += 1
        self.segment_idx += 1

    # -- driving -----------------------------------------------------------

    def _pre_segment(self) -> None:
        """Hook between admission and the decode segment (paged lazy
        block extension / preemption)."""

    def step(self) -> None:
        """Admit waiting requests, then run one decode segment."""
        self._admit()
        self._pre_segment()
        if (self.slot_uid >= 0).any():
            self._segment()

    def run(self) -> Dict[int, Completion]:
        """Drain the queue: segments with admission in between."""
        t0 = time.perf_counter()
        while not self.idle:
            self.step()
        self.stats["wall_s"] = (self.stats.get("wall_s", 0.0)
                                + time.perf_counter() - t0)
        return self.completions


class PagedServeEngine(ServeEngine):
    """Continuous batching over a block-paged KV cache.

    A request is admitted holding blocks from the shared pool, full
    prompt blocks dedup'd against the allocator's content pool, so
    concurrency is bounded by *live tokens* (plus per-request round-up)
    instead of ``n_slots * max_len``.

    With ``lazy=True`` (default) admission claims only the blocks the
    PROMPT spans; decode blocks are claimed per segment as the write
    frontier crosses block boundaries (``_pre_segment``), so a request
    holds memory proportional to what it has actually generated —
    long-``max_new`` traffic no longer reserves its worst case up
    front.  If the pool runs dry between segments the youngest-admitted
    live request is preempted: its blocks return to the pool and the
    request re-queues for a deterministic replay (same per-request key,
    so its final tokens are unchanged).  The oldest request is never
    preempted, which guarantees forward progress.  ``lazy=False``
    restores the PR 4 behavior: ``ceil(decode_capacity / block_len)``
    blocks at admission, tables fixed for the request's lifetime.
    """

    def __init__(self, params, cfg: ModelConfig, *, block_len: int = 16,
                 n_blocks: Optional[int] = None, n_slots: int = 4,
                 max_len: int = 128, share_prefix: bool = True,
                 lazy: bool = True, **kw):
        self.block_len = block_len
        self.max_blocks = -(-max_len // block_len)
        # speculative verify chunks write up to k positions past the
        # accepted frontier; a full-capacity slot would overflow its last
        # real table column (gathers CLAMP, aliasing the final block), so
        # the table gets spare always-TRASH columns to absorb overshoot
        spec = int(kw.get("speculate", 0) or 0)
        self._spec_spare = -(-spec // block_len) if spec else 0
        # default pool: worst case every slot holds max_len live tokens
        self.n_blocks = (1 + n_slots * self.max_blocks
                         if n_blocks is None else n_blocks)
        self._has_paged = M.has_paged_leaves(cfg)
        self.share_prefix = share_prefix and self._has_paged
        self.lazy = lazy and self._has_paged
        # per-shard free lists mirror the pool sharding: each device owns
        # a contiguous run of block ids (rules.paged_cache_specs), so the
        # allocator can keep every shard's block population balanced
        mesh = kw.get("mesh")
        n_shards = 1
        if mesh is not None and mesh.size > 1:
            n_data = 1
            for a in ("pod", "data"):
                if a in mesh.axis_names:
                    n_data *= mesh.shape[a]
            if n_data > 1 and self.n_blocks % n_data == 0:
                n_shards = n_data
        self.alloc = pg.PagedAllocator(self.n_blocks, block_len,
                                       n_shards=n_shards)
        self._table_w = self.max_blocks + self._spec_spare
        self.block_tables = np.full((n_slots, self._table_w), pg.TRASH,
                                    np.int32)
        self._slot_blocks: Dict[int, List[int]] = {}  # uid -> held block ids
        super().__init__(params, cfg, n_slots=n_slots, max_len=max_len, **kw)
        self.stats.update({"shared_blocks": 0, "fresh_blocks": 0,
                           "peak_live_blocks": 0, "lazy_claimed_blocks": 0,
                           "preemptions": 0})

    # -- cache layout ------------------------------------------------------

    def _init_cache(self) -> None:
        self.cache = M.init_paged_cache(self.cfg, self.n_slots, self.n_blocks,
                                        self.block_len, mesh=self.mesh,
                                        policy=self.policy)
        self._cache_shardings = self._shardings_of(self.cache)

    def _build_admit(self, key):
        if self.chunk_len is not None:
            return self._build_admit_chunked(key)
        cfg, bl = self.cfg, self.block_len
        n_pb = -(-M.decode_pos0(cfg, key) // bl)  # blocks holding prompt rows

        def admit(cache, pc, slot, ids, mask):
            sub = M.prefill_into_cache(
                cfg, M.init_decode_cache(cfg, 1, n_pb * bl), pc)
            return self._constrain_cache(
                M.scatter_prefill_paged(cfg, cache, sub, slot, ids, mask,
                                        block_len=bl))

        return jax.jit(admit, donate_argnums=(0,))

    def _build_admit_chunked(self, rung: int):
        """Chunked admission against the paged layout: slot-resident
        leaves are sliced to a B=1 view, pool leaves pass through whole
        and the chunk writes flow through the (rung-wide) read/write
        tables — the write table diverts already-pooled shared prefix
        rows to the trash block so chunked re-computation can never
        perturb content other requests are reading."""
        cfg, mesh, C = self.cfg, self.mesh, self.chunk_len
        bat = M.decode_cache_batch_axes(cfg, policy=self.policy)
        seq = M.decode_cache_seq_axes(cfg, policy=self.policy)

        def admit(params, cache, batch, prompt_len, slot, read_tbl,
                  write_tbl):
            s1 = jnp.reshape(slot, (1,))
            sub = jax.tree.map(
                lambda leaf, bax, sax: leaf if sax >= 0 else
                jnp.take(leaf, s1, axis=bax),
                cache, bat, seq)
            logits, sub = M.prefill_chunked(params, cfg, sub, batch,
                                            prompt_len, chunk_len=C,
                                            mesh=mesh, block_tables=read_tbl,
                                            write_tables=write_tbl)
            cache = self._constrain_cache(
                _scatter_slot_row(cache, sub, slot, bat, seq))
            return logits, cache

        return jax.jit(admit, donate_argnums=(1,))

    # -- admission ---------------------------------------------------------

    def _validate_capacity(self, uid: int, P: int, max_new: int) -> None:
        super()._validate_capacity(uid, P, max_new)
        if not self._has_paged:
            return
        n_total = -(-M.decode_capacity(self.cfg, P, max_new)
                    // self.block_len)
        if n_total > self.n_blocks - 1:
            # admission could otherwise stall forever waiting for blocks
            # the pool can never provide, even with every slot free
            raise ValueError(
                f"request {uid}: needs {n_total} blocks > pool of "
                f"{self.n_blocks - 1} allocatable blocks")

    def _n_total_blocks(self, req: Request) -> int:
        return -(-M.decode_capacity(self.cfg, req.prompt_len, req.max_new)
                 // self.block_len)

    def _plan(self, req: Request):
        rung = (self._bucket_rung(req.prompt_len)
                if self.chunk_len is not None else None)
        if not self._has_paged:
            return {"rung": rung, "keys": [], "n_pb": 0, "n_alloc": 0,
                    "missing": 0}
        bl = self.block_len
        pos0 = M.decode_pos0(self.cfg, req.prompt_len)
        n_total = self._n_total_blocks(req)
        n_pb = -(-pos0 // bl)
        if req.plan_keys is None:
            req.plan_keys = (pg.prefix_keys(req.batch, pos0 // bl, bl,
                                            M.decode_offset(self.cfg),
                                            policy=self.kv_dtype)
                             if self.share_prefix else [])
        keys = req.plan_keys
        # lazy admission claims only the prompt's blocks; the rest are
        # claimed per segment as the write frontier crosses boundaries
        n_alloc = n_pb if self.lazy else n_total
        # the lookup part IS re-evaluated per attempt: pool contents
        # change between segments while the request waits for blocks
        missing = n_alloc - sum(1 for k in keys
                                if self.alloc.lookup(k) is not None)
        return {"rung": rung, "keys": keys, "n_pb": n_pb, "n_alloc": n_alloc,
                "missing": missing}

    def _fits(self, plan) -> bool:
        return plan["missing"] <= self.alloc.n_free

    def _acquire_blocks(self, uid: int, plan):
        """Claim the plan's blocks: shared ``acquire`` for full prompt
        blocks, private ``alloc`` from the partial tail onward (decode
        writes and diverged suffixes must never alias).  Returns
        (ids, fresh) — ``fresh[i]`` False iff block i was pooled."""
        keys = plan["keys"]
        ids, fresh = [], []
        for i in range(plan["n_alloc"]):
            if i < len(keys):
                bid, fr = self.alloc.acquire(keys[i])
                self.stats["shared_blocks" if not fr
                           else "fresh_blocks"] += 1
            else:
                bid, fr = self.alloc.alloc(), True
                self.stats["fresh_blocks"] += 1
            ids.append(bid)
            fresh.append(fr)
        self._slot_blocks[uid] = ids
        self.stats["peak_live_blocks"] = max(self.stats["peak_live_blocks"],
                                             self.alloc.n_live)
        return ids, fresh

    def _set_table_row(self, slot: int, ids) -> None:
        # ids never exceed max_blocks, so the _spec_spare tail columns
        # stay TRASH for the slot's whole lifetime: speculative writes
        # past capacity are diverted, never aliased onto a real block
        row = np.full((self._table_w,), pg.TRASH, np.int32)
        row[:len(ids)] = ids
        self.block_tables[slot] = row

    def _place(self, slot: int, req: Request, pc, plan) -> None:
        ids, fresh = self._acquire_blocks(req.uid, plan)
        n_pb = plan["n_pb"]
        self._set_table_row(slot, ids)
        self.cache = self._admit_exec(req.prompt_len)(
            self.cache, pc, slot, jnp.asarray(ids[:n_pb], jnp.int32),
            jnp.asarray(fresh[:n_pb], jnp.bool_))

    def _admit_chunked_into(self, slot: int, req: Request, plan):
        rung, bl = plan["rung"], self.block_len
        W = -(-rung // bl)  # wide enough for every padded position
        read = np.full((1, W), pg.TRASH, np.int32)
        write = np.full((1, W), pg.TRASH, np.int32)
        if self._has_paged:
            ids, fresh = self._acquire_blocks(req.uid, plan)
            # admission tables carry the PROMPT blocks only (n_pb <= W
            # since pos0 <= rung): chunk writes never touch decode
            # blocks — pads beyond the prompt land in the trash block —
            # so eager mode's extra n_total - n_pb blocks stay out of
            # the (rung-keyed, fixed-width) admission operands and only
            # enter the segment tables below
            n_pb = plan["n_pb"]
            read[0, :n_pb] = ids[:n_pb]
            write[0, :n_pb] = [bid if fr else pg.TRASH
                               for bid, fr in zip(ids[:n_pb], fresh[:n_pb])]
            self._set_table_row(slot, ids)
        logits, self.cache = self._admit_exec(rung)(
            self.params, self.cache, self._padded_batch(req, rung),
            jnp.int32(req.prompt_len), jnp.int32(slot),
            jnp.asarray(read), jnp.asarray(write))
        return logits

    def _rollback_place(self, slot: int, req: Request) -> None:
        for bid in self._slot_blocks.pop(req.uid, []):
            self.alloc.release(bid)
        self.block_tables[slot] = pg.TRASH
        self.pos[slot] = 0

    def _release_slot(self, slot: int) -> None:
        uid = int(self.slot_uid[slot])
        super()._release_slot(slot)
        for bid in self._slot_blocks.pop(uid, []):
            self.alloc.release(bid)
        # dead lane: writes pin to (trash block, offset 0) until re-admitted
        self.block_tables[slot] = pg.TRASH
        self.pos[slot] = 0

    # -- lazy per-segment block claiming + preemption ----------------------

    def _segment_needs(self) -> Dict[int, int]:
        """slot -> blocks to claim so the coming segment's writes stay
        inside the slot's table (frontier can advance min(seg_len, rem)
        positions; capacity-capped)."""
        bl, needs = self.block_len, {}
        for s in range(self.n_slots):
            uid = int(self.slot_uid[s])
            if uid < 0:
                continue
            adv = int(min(self.seg_len * (self.speculate + 1), self.rem[s]))
            if adv <= 0:
                continue
            # + speculate: the step that lands the last accepted token
            # also wrote its rejected draft tail past the frontier
            last_write = int(self.pos[s]) + adv - 1 + self.speculate
            n_total = self._n_total_blocks(self._live_req[uid])
            need = min(last_write // bl + 1, n_total)
            have = len(self._slot_blocks[uid])
            if need > have:
                needs[s] = need - have
        return needs

    def _preempt_youngest(self) -> None:
        """Return the youngest-admitted live request to the queue (its
        blocks go back to the pool; replay is deterministic, so its
        final tokens are unaffected)."""
        live = [s for s in range(self.n_slots) if self.slot_uid[s] >= 0]
        if len(live) <= 1:
            # unreachable: submit() rejects requests larger than the pool
            raise RuntimeError("paged pool exhausted by a single request")
        s = max(live, key=lambda s: self._slot_seq[s])
        uid = int(self.slot_uid[s])
        req = self._live_req.pop(uid)
        # roll back the discarded work so token/utilization stats only
        # count emissions that reach a completion (emission #1 came from
        # the prefill, not a slot step)
        discarded = self._out.pop(uid)
        self.stats["generated_tokens"] -= len(discarded)
        self.stats["live_slot_steps"] -= len(discarded) - 1
        self._plen.pop(uid)
        self._nseg.pop(uid)
        self.slot_uid[s] = -1
        self.rem[s] = 0
        self._rollback_place(s, req)
        self.queue.appendleft(req)  # admitted before anything still queued
        self._pending.add(uid)
        self.stats["preemptions"] += 1

    def _pre_segment(self) -> None:
        if not self._has_paged:
            return
        needs = self._segment_needs()
        while sum(needs.values()) > self.alloc.n_free:
            self._preempt_youngest()
            needs = self._segment_needs()
        for s, n in needs.items():
            ids = self._slot_blocks[int(self.slot_uid[s])]
            for _ in range(n):
                bid = self.alloc.alloc()
                self.block_tables[s, len(ids)] = bid
                ids.append(bid)
            self.stats["lazy_claimed_blocks"] += n
            self.stats["fresh_blocks"] += n
        if needs:
            self.stats["peak_live_blocks"] = max(
                self.stats["peak_live_blocks"], self.alloc.n_live)

    # -- scanned decode segment --------------------------------------------

    def _run_segment(self):
        return M.generate(self.params, self.cfg, self.cache,
                          jnp.asarray(self.tok), jnp.asarray(self.pos),
                          steps=self.seg_len, sampler=self.sampler,
                          rng=jnp.asarray(self.keys), eos_id=self.eos_id,
                          remaining=jnp.asarray(self.rem), mesh=self.mesh,
                          block_tables=jnp.asarray(self.block_tables),
                          **self._spec_kw())
