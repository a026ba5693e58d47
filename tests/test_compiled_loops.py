"""Equivalence + accounting tests for the compiled federated hot loops.

The scan-compiled epoch drivers (device local training, Phase II
distillation, Phase III tuning) and the vmapped fleet driver must
reproduce the historical per-step Python loops at fixed seeds — same
batches, same lr schedule, same updates.  Also pins the comm-cost
accounting fix: uploads are billed from the *configured* device model's
parameter count (Eq. 5 / Fig. 8), not the in-memory reduced tree.
"""
import collections
import logging
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import distill, tuning
from repro.core import vaa as vaa_mod
from repro.data.federated import FederatedCorpus
from repro.federated.device import (DeviceSpec, _device_init, _init_bucket,
                                    device_upload_bytes, train_device,
                                    train_fleet)
from repro.models import model as M
from repro.models.config import ModelConfig
from repro.optim import adamw_init, adamw_update, cosine_schedule
from repro.utils import scopes
from repro.utils.pytree import tree_bytes

V = 64
SMALL = dict(vocab_size=V, dtype="float32", remat=False,
             attn_chunk_q=16, attn_chunk_k=16, loss_chunk=16)

CFG_A = ModelConfig(name="scan-a-tiny", n_layers=1, d_model=32, n_heads=2,
                    n_kv_heads=2, head_dim=16, d_ff=64,
                    norm_type="layernorm", act="gelu", mlp_gated=False,
                    pos_embedding="sinusoidal", **SMALL).validate()
CFG_B = ModelConfig(name="scan-b-tiny", n_layers=2, d_model=48, n_heads=2,
                    n_kv_heads=2, head_dim=24, d_ff=96, **SMALL).validate()
MOE_CFG = ModelConfig(name="scan-moe-tiny", arch_type="moe", n_layers=2,
                      d_model=32, n_heads=2, n_kv_heads=2, head_dim=16,
                      d_ff=64, n_experts=4, top_k=2, moe_d_ff=64,
                      n_shared_experts=1, **SMALL).validate()

STEPS, BATCH, SEQ = 5, 4, 16


@pytest.fixture(scope="module")
def corpus():
    return FederatedCorpus.build(seed=0, n_devices=5, n_domains=2, vocab=V)


@pytest.fixture(scope="module")
def fleet():
    return [DeviceSpec(0, CFG_A, 0, 0), DeviceSpec(1, CFG_B, 1, 0),
            DeviceSpec(2, CFG_A, 0, 1), DeviceSpec(3, CFG_A, 0, 1),
            DeviceSpec(4, CFG_B, 1, 1)]


def _tree_max_diff(a, b):
    return max(float(jnp.max(jnp.abs(x.astype(jnp.float32) -
                                     y.astype(jnp.float32))))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


# ---------------------------------------------------------------------------
# device local training
# ---------------------------------------------------------------------------

def test_device_scan_matches_per_step(corpus):
    kw = dict(steps=STEPS, batch=BATCH, seq_len=SEQ, seed=0)
    spec = DeviceSpec(0, CFG_A, 0, 0)
    ref = train_device(spec, corpus, compiled=False, **kw)
    got = train_device(spec, corpus, compiled=True, **kw)
    # one compiled scan over pre-generated batches == the per-step loop,
    # bit for bit
    np.testing.assert_array_equal(np.array(got["losses"]),
                                  np.array(ref["losses"]))
    assert _tree_max_diff(got["params"], ref["params"]) == 0.0


def test_fleet_vmap_matches_per_device(corpus, fleet):
    kw = dict(steps=STEPS, batch=BATCH, seq_len=SEQ, seed=0)
    refs = [train_device(s, corpus, compiled=False, **kw) for s in fleet]
    got = train_fleet(fleet, corpus, **kw)
    assert [u["device_id"] for u in got] == [s.device_id for s in fleet]
    for r, g, spec in zip(refs, got, fleet):
        # vmap batches the per-device programs; XLA may re-associate the
        # loss reductions, so allow float32 round-off on the recorded
        # losses (parameters come out bit-identical in practice)
        np.testing.assert_allclose(np.array(g["losses"]),
                                   np.array(r["losses"]),
                                   rtol=0, atol=5e-6)
        assert _tree_max_diff(g["params"], r["params"]) < 1e-6
        assert g["arch_id"] == r["arch_id"] == spec.arch_id
        assert g["upload_bytes"] == r["upload_bytes"]
        np.testing.assert_array_equal(g["embedding"], r["embedding"])
        # uploads wait for the server in host memory, not on the device
        assert all(isinstance(x, np.ndarray)
                   for x in jax.tree.leaves(g["params"]))


@pytest.mark.parametrize("policy", ["", "bf16", "int8"])
def test_init_bucket_equals_stacked_device_inits(fleet, policy):
    specs = [s for s in fleet if s.cfg == CFG_A]
    params, opt = _init_bucket(specs, 3, policy)
    inits = [_device_init(s, 3, policy) for s in specs]
    stack = lambda *xs: np.stack(xs)
    for got, want in ((params, jax.tree.map(stack, *[p for p, _ in inits])),
                      (opt, jax.tree.map(stack, *[o for _, o in inits]))):
        assert jax.tree.structure(got) == jax.tree.structure(want)
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            assert g.dtype == w.dtype
            np.testing.assert_array_equal(np.asarray(g), w)


# ---------------------------------------------------------------------------
# Phase II distillation
# ---------------------------------------------------------------------------

def test_distill_epoch_matches_per_step(corpus):
    hp = dict(alpha=1.0, beta=1.0, temperature=2.0, n_stages=2,
              vaa_heads=2, p_q=8)
    lr, warmup = 1e-3, 1
    t_params = M.init_params(jax.random.PRNGKey(7), CFG_B)
    s_params = M.init_params(jax.random.PRNGKey(8), CFG_A)
    vaa_params = vaa_mod.init_vaa(jax.random.PRNGKey(9), n_stages=2,
                                  d_student=CFG_A.d_model,
                                  d_teacher=CFG_B.d_model, d=16, n_heads=2,
                                  p_q=8)
    trainable = {"student": s_params, "vaa": vaa_params}

    step = jax.jit(distill.make_distill_step(
        CFG_A, CFG_B, optimizer_update=adamw_update, **hp))
    sched = cosine_schedule(lr, STEPS, warmup=warmup)
    ref_t, ref_o = trainable, adamw_init(trainable)
    ref_losses = []
    for s in range(STEPS):
        b = corpus.mixed_eval_batch(BATCH, SEQ, seed_salt=s)
        ref_t, ref_o, loss, _ = step(ref_t, ref_o, t_params, b, sched(s))
        ref_losses.append(float(loss))

    epoch = jax.jit(distill.make_distill_epoch(
        CFG_A, CFG_B, steps=STEPS, schedule=sched,
        optimizer_update=adamw_update, **hp))
    batches = corpus.mixed_eval_batches(STEPS, BATCH, SEQ)
    got_t, _, losses = epoch(trainable, adamw_init(trainable), t_params,
                             batches)
    # compiling the whole epoch as one program lets XLA re-associate the
    # chunked CE/KL reductions — allow float32 ulp-level round-off
    np.testing.assert_allclose(np.asarray(losses), np.array(ref_losses),
                               rtol=0, atol=5e-6)
    assert _tree_max_diff(got_t, ref_t) < 1e-5


# ---------------------------------------------------------------------------
# Phase III tuning
# ---------------------------------------------------------------------------

def test_tune_epoch_matches_per_step(corpus):
    lr, warmup = 5e-4, 1
    params = M.init_params(jax.random.PRNGKey(11), MOE_CFG)
    mask, opt0 = tuning.init_tuning(params)
    sched = cosine_schedule(lr, STEPS, warmup=warmup)

    step = jax.jit(tuning.make_tune_step(MOE_CFG, mask))
    ref_p, ref_o = params, opt0
    ref_losses = []
    for s in range(STEPS):
        b = corpus.mixed_eval_batch(BATCH, SEQ, seed_salt=10_000 + s)
        ref_p, ref_o, loss, _ = step(ref_p, ref_o, b, sched(s))
        ref_losses.append(float(loss))

    epoch = jax.jit(tuning.make_tune_epoch(MOE_CFG, mask, steps=STEPS,
                                           schedule=sched))
    batches = corpus.mixed_eval_batches(STEPS, BATCH, SEQ, seed_salt0=10_000)
    _, opt0b = tuning.init_tuning(params)
    got_p, _, losses = epoch(params, opt0b, batches)
    np.testing.assert_allclose(np.asarray(losses), np.array(ref_losses),
                               rtol=0, atol=5e-6)
    assert _tree_max_diff(got_p, ref_p) < 1e-5


# the tapped tune epoch: 3 MoE layers, widths chosen so that no weight
# of another leaf and no saved activation (T = 2 x 24 rows) has the
# stacked shape of a frozen leaf
TAP_CFG = MOE_CFG.replace(name="scan-moe-tap", n_layers=3, n_experts=8,
                          moe_d_ff=40)
_METADATA = re.compile(r",? ?metadata=\{[^}]*\}")
_FIRST = re.compile(r"^(%|ENTRY)", re.M)   # tables of source files precede


def _optimized_hlo(fn, *args):
    # a fresh function each time, so that nothing traced before is reused
    text = jax.jit(lambda *a: fn(*a)).lower(*args).compile().as_text()
    return _METADATA.sub("", text[_FIRST.search(text).start():])


def _shape(text):
    return tuple(int(d) for d in text.split(",") if d)


def test_tune_epoch_writes_no_frozen_gradient_stack(monkeypatch, caplog):
    """A frozen leaf reaches the clip as a squared norm per layer, taken
    inside the layer scan: no ``dynamic-update-slice`` writes a buffer of
    a frozen leaf's stacked shape, and each ``while`` loop carries such a
    buffer only as often as there are frozen weights of that shape (the
    weights themselves, read by the forward and the backward)."""
    from repro.federated import server
    monkeypatch.setattr(tuning, "_TAP_LOGGED", set())
    caplog.set_level(logging.INFO, logger=tuning.__name__)
    params = M.init_params(jax.random.PRNGKey(0), TAP_CFG)
    mask, opt = tuning.init_tuning(params)
    frozen = [p for p, m in zip(jax.tree.leaves(params),
                                jax.tree.leaves(mask)) if not m]
    weights = collections.Counter(tuple(p.shape) for p in frozen)
    assert len(frozen) == 6 and all(len(s) >= 3 for s in weights)
    tok = jnp.zeros((2, 2, 24), jnp.int32)
    text = server._tune_epoch_fn(TAP_CFG, None, mask, 2, 1e-3, 0).lower(
        params, opt, {"tokens": tok, "labels": tok}).compile().as_text()

    updates = re.findall(r"= \w+\[([\d,]*)\]\S* dynamic-update-slice\(",
                         text)
    assert updates and not {_shape(u) for u in updates} & set(weights)
    loops = re.findall(r"= \((.*)\) while\(", text)
    assert len(loops) >= 2
    for carried in loops:
        shapes = collections.Counter(
            _shape(d) for d in re.findall(r"\w+\[([\d,]*)\]", carried))
        assert all(shapes[s] <= n for s, n in weights.items()), shapes
    assert [r.getMessage() for r in caplog.records] == [
        f"frozen taps: 6 leaves, {sum(p.nbytes for p in frozen)} gradient "
        "bytes not materialised"]


def _parent_scan(cfg, body, init, xs):
    """``model._scan`` as it was before frozen leaves were tapped."""
    return jax.lax.scan(body, init, xs, unroll=M._unroll(cfg, xs))


@pytest.mark.parametrize("program", ["distill_step", "prefill"])
def test_untapped_programs_unchanged(monkeypatch, program):
    """A scan whose layers carry no ``Tapped`` leaf compiles to the
    program it compiled to before the taps: the Phase II distillation
    step and a MoE prefill, optimized HLO without metadata."""
    if program == "distill_step":
        t_params = M.init_params(jax.random.PRNGKey(7), CFG_B)
        trainable = {"student": M.init_params(jax.random.PRNGKey(8), CFG_A),
                     "vaa": vaa_mod.init_vaa(
                         jax.random.PRNGKey(9), n_stages=2,
                         d_student=CFG_A.d_model, d_teacher=CFG_B.d_model,
                         d=16, n_heads=2, p_q=8)}
        tok = jnp.zeros((BATCH, SEQ), jnp.int32)
        fn = distill.make_distill_step(
            CFG_A, CFG_B, optimizer_update=adamw_update, alpha=1.0,
            beta=1.0, temperature=2.0, n_stages=2, vaa_heads=2, p_q=8)
        args = (trainable, adamw_init(trainable), t_params,
                {"tokens": tok, "labels": tok}, 1e-3)
    else:
        params = M.init_params(jax.random.PRNGKey(0), TAP_CFG)
        fn = lambda p, b: M.prefill(p, TAP_CFG, b)
        args = (params, {"tokens": jnp.zeros((2, 24), jnp.int32)})
    now = _optimized_hlo(fn, *args)
    monkeypatch.setattr(M, "_scan", scopes.scoped(scopes.STACK)(_parent_scan))
    assert now == _optimized_hlo(fn, *args)


# ---------------------------------------------------------------------------
# stacked batch generation contract
# ---------------------------------------------------------------------------

def test_stacked_batches_match_per_step_batches(corpus):
    stacked = corpus.device_batches(1, STEPS, BATCH, SEQ)
    assert stacked["tokens"].shape == (STEPS, BATCH, SEQ)
    for s in range(STEPS):
        b = corpus.device_batch(1, BATCH, SEQ, step=s)
        np.testing.assert_array_equal(np.asarray(stacked["tokens"][s]),
                                      np.asarray(b["tokens"]))
        np.testing.assert_array_equal(np.asarray(stacked["labels"][s]),
                                      np.asarray(b["labels"]))
    stacked = corpus.mixed_eval_batches(STEPS, BATCH, SEQ, seed_salt0=3)
    for s in range(STEPS):
        b = corpus.mixed_eval_batch(BATCH, SEQ, seed_salt=3 + s)
        np.testing.assert_array_equal(np.asarray(stacked["tokens"][s]),
                                      np.asarray(b["tokens"]))


# ---------------------------------------------------------------------------
# comm-cost accounting (Eq. 5 / Fig. 8)
# ---------------------------------------------------------------------------

def test_upload_bytes_from_configured_model():
    # billed from the config's param count at its configured dtype —
    # identical to the materialised tree for a directly-trained config
    p = M.init_params(jax.random.PRNGKey(0), CFG_A)
    assert device_upload_bytes(CFG_A) == tree_bytes(p) + 32 * 4


def test_upload_bytes_pins_gpt2():
    # GPT-2 (paper device model): 123,570,432 params @ bf16 + 32-float
    # embedding = 247,140,992 bytes one-shot upload
    from repro.configs.device_models import GPT2
    assert device_upload_bytes(GPT2) == 247_140_992


def test_build_fleet_plumbs_full_cfgs(corpus):
    # the simulation API can bill full-size models while training the
    # reduced stand-ins: full_cfgs maps each family to its paper model
    from repro.configs.device_models import GPT2, GPT2_MEDIUM
    from repro.federated.simulation import SimulationConfig, build_fleet
    sim = SimulationConfig(n_devices=5, n_domains=2, vocab=V, seq_len=SEQ)
    fleet = build_fleet(sim, corpus, [CFG_A, CFG_B],
                        full_cfgs=[GPT2, GPT2_MEDIUM])
    assert {s.arch_id for s in fleet} == {0, 1}
    for spec in fleet:
        assert spec.comm_cfg is (GPT2 if spec.arch_id == 0 else GPT2_MEDIUM)


def test_fleet_bills_full_variant_not_trained_reduction(corpus):
    # a device that trains a reduced CPU stand-in still bills the
    # configured full-size model's upload (module docstring contract)
    from repro.configs.device_models import GPT2
    spec = DeviceSpec(0, CFG_A, 0, 0, full_cfg=GPT2)
    up = train_device(spec, corpus, steps=2, batch=2, seq_len=8, seed=0)
    assert up["upload_bytes"] == device_upload_bytes(GPT2)
    assert up["upload_bytes"] > tree_bytes(up["params"])
