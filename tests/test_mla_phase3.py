"""Phase III of a DeepSeek-V3-architecture MoE (multi-head latent
attention, a leading dense layer, the sigmoid router with its correction
bias) through the server's jitted tuning epoch, against the benchmark's
plain float32 reference (``bench/reference/mla_moe_lm.py``, which
imports nothing of the program); and the Qwen1.5-MoE-shaped Phase III
program left as it was by the router's and the norm's new options."""
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import tuning
from repro.federated import server
from repro.models import model as M

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")

# Moonlight-16B-A3B's block at CPU size: MLA with a full-rank query, one
# dense layer and two expert layers of 8 experts, top-3, 2 shared
TINY = {"hidden_size": 64, "intermediate_size": 96,
        "moe_intermediate_size": 32, "n_routed_experts": 8,
        "num_experts_per_tok": 3, "n_shared_experts": 2,
        "num_hidden_layers": 3, "num_attention_heads": 4,
        "num_key_value_heads": 4, "kv_lora_rank": 16,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
        "vocab_size": 256, "torch_dtype": "float32"}
HP = {"lr": 5e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8, "wd": 0.01,
      "clip": 1.0}


@pytest.fixture(scope="module")
def bench_modules():
    sys.path.insert(0, BENCH)
    try:
        from harness.spec import load_module
        from reference import mla_moe_lm
        return load_module, mla_moe_lm
    finally:
        sys.path.remove(BENCH)


def _path(keys):
    return "/".join(str(getattr(k, "key", k)) for k in keys)


def test_moonlight_phase3_matches_plain_reference(bench_modules):
    load_module, ref = bench_modules
    with open(os.path.join(BENCH, "configs",
                           "moonlight-16b-a3b.tune.json")) as f:
        cfg = dict(json.load(f), **TINY)
    arch = load_module("reference", "deepseek_v3").arch(cfg)
    mcfg = load_module("systems", "deepseek_v3").program_config(cfg)
    assert (mcfg.attn_type, mcfg.q_lora_rank, mcfg.first_dense_layers,
            mcfg.router_score, mcfg.norm_eps) == ("mla", 0, 1, "sigmoid",
                                                  1e-5)
    shapes = ref.param_shapes(arch)
    abstract = jax.eval_shape(lambda k: M.init_params(k, mcfg),
                              jax.random.PRNGKey(0))
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    paths = [_path(p) for p, _ in flat]
    assert {p: (tuple(x.shape), str(x.dtype)) for p, (_, x)
            in zip(paths, flat)} == shapes
    seed = 2 ** 32 + 17
    made = ref.make(seed, shapes)
    # copies: the epoch donates what it is given
    params = treedef.unflatten([jnp.array(made[p]) for p in paths])
    mask, opt = tuning.init_tuning(params)
    trainable = [p for p, m in zip(paths, jax.tree.leaves(mask)) if m]
    assert not any("e_score_correction_bias" in p for p in trainable)

    rng = np.random.default_rng(0)
    rows = rng.integers(0, arch["V"], (3, 2, 33)).astype(np.int32)
    epoch = server._tune_epoch_fn(mcfg, None, mask, 1, HP["lr"], 0)
    losses, grad = [], None
    for r in rows:
        params, opt, loss = epoch(params, opt, {
            "tokens": jnp.asarray(r[None, :, :-1]),
            "labels": jnp.asarray(r[None, :, 1:])})
        losses.append(float(loss[0]))
        if grad is None:     # the optimizer's first moment after step 1
            m = dict(zip(paths, jax.tree.leaves(opt["m"])))
            grad = {p: float(jnp.linalg.norm(m[p])) / (1 - HP["b1"])
                    for p in trainable}
    now = dict(zip(paths, jax.tree.leaves(params)))
    change = {k: float(v) for k, v in ref.change_norms(now, made).items()}

    want = ref.tune_readings(   # copies again: its steps donate too
        arch, lambda keep=None: {p: jnp.array(x) for p, x in made.items()
                                 if keep is None or keep(p)},
        [(jnp.asarray(r[:, :-1]), jnp.asarray(r[:, 1:])) for r in rows], HP)
    # both sides compute in float32 from the same weights and rows; they
    # differ only in the order of their sums, a few ulps a product, which
    # three steps of AdamW keep far under 1e-5
    np.testing.assert_allclose(losses, want["loss"], rtol=1e-5)
    assert sorted(grad) == sorted(want["grad"])
    for p in trainable:
        np.testing.assert_allclose(grad[p], want["grad"][p], rtol=1e-5,
                                   atol=1e-7, err_msg=p)
    for p, v in want["change"].items():
        np.testing.assert_allclose(change[p], v, rtol=1e-5, atol=1e-7,
                                   err_msg=p)
    bias = "blocks/sub0/moe/e_score_correction_bias"
    assert change[bias] == want["change"][bias] == 0.0
    assert np.any(np.asarray(now[bias]))    # a bias that was there to keep


_METADATA = re.compile(r",? ?metadata=\{[^}]*\}")
# the first computation: what precedes it are tables of source files,
# functions and stack frames, which name where the program was traced
_FIRST = re.compile(r"^(%|ENTRY)", re.M)


def _qwen_tune_hlo(**fields):
    cfg = get_config("qwen2-moe-a2.7b", variant="reduced").replace(**fields)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    mask, opt = tuning.init_tuning(params)
    tok = jnp.zeros((1, 2, 32), jnp.int32)
    epoch = server._tune_epoch_fn(cfg, None, mask, 1, 1e-3, 0)
    text = epoch.lower(params, opt, {"tokens": tok, "labels": tok}
                       ).compile().as_text()
    return _METADATA.sub("", text[_FIRST.search(text).start():])


def test_qwen_phase3_program_is_unchanged():
    """The router's and the norm's fields default to what the program did
    before it had them: softmax scores, one group, unscaled weights and
    a norm epsilon of 1e-6, so the Qwen1.5-MoE-shaped Phase III program
    is the one those values spell out."""
    assert _qwen_tune_hlo() == _qwen_tune_hlo(
        router_score="softmax", n_group=1, topk_group=1,
        routed_scaling_factor=1.0, norm_eps=1e-6)
