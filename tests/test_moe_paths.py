"""The MoE execution paths against the all-experts reference
``moe_dense``.

The one-device ``grouped`` path is compared in process, on value and
gradients.  The sharded paths (a2a / replicated_ep) need a forced
multi-device CPU backend: XLA's host device count is locked at backend
init, so they run in a subprocess with XLA_FLAGS set — the only way to
exercise the shard_map paths (and their shared dispatch/combine slot
layout) under pytest.
"""
import logging
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.models import model as M
from repro.models import moe
from repro.models.config import ModelConfig

# Qwen1.5-MoE-style (a shared lane of whole experts, top-4 of 8) and
# DeepSeek-MoE-style (fine-grained: top-6 of 16, two shared experts)
_GROUPED_CFGS = {
    "qwen": dict(n_experts=8, top_k=4, moe_d_ff=24, n_shared_experts=4),
    "deepseek": dict(n_experts=16, top_k=6, moe_d_ff=12,
                     n_shared_experts=2),
    # DeepSeek-V3's router: sigmoid scores, a correction bias that only
    # selects, top-2 of 4 groups, scaled weights, sequence-wise balance
    "sigmoid": dict(n_experts=16, top_k=4, moe_d_ff=12, n_shared_experts=2,
                    router_score="sigmoid", n_group=4, topk_group=2,
                    routed_scaling_factor=2.5),
}


def _tiny(family, **kw):
    return ModelConfig(name=family, arch_type="moe", n_layers=1, d_model=32,
                       n_heads=2, n_kv_heads=2, head_dim=16, d_ff=64,
                       vocab_size=64, dtype="float32",
                       **_GROUPED_CFGS[family], **kw).validate()


def _routing_case(cfg, case):
    """(params, x, live) for one routing case."""
    p = moe.init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
    if cfg.router_score == "sigmoid":
        p["e_score_correction_bias"] = 0.1 * jax.random.normal(
            jax.random.PRNGKey(3), (cfg.n_experts,))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 12, cfg.d_model))
    live = None
    if case == "skewed":
        # every token's top-k are the same k experts: most groups empty
        bias = jnp.zeros((cfg.n_experts,)).at[:cfg.top_k].set(
            jnp.arange(cfg.top_k, 0, -1) * 10.0)
        x = x.at[..., 0].set(1.0)
        p = dict(p, router=p["router"].at[0].add(bias))
        if cfg.router_score == "sigmoid":   # scores saturate: bias selects
            p["e_score_correction_bias"] = p["e_score_correction_bias"] + bias
    elif case == "live":
        live = jnp.arange(24).reshape(2, 12) % 3 != 0
    return p, x, live


@pytest.mark.parametrize("case", ["random", "skewed", "live"])
@pytest.mark.parametrize("family", sorted(_GROUPED_CFGS))
def test_grouped_matches_dense(family, case):
    cfg = _tiny(family)
    p, x, live = _routing_case(cfg, case)
    ct = jax.random.normal(jax.random.PRNGKey(2), x.shape)

    def loss(impl):
        c = cfg.replace(moe_impl=impl)

        def f(p, x):
            out, aux = moe.apply_moe(p, c, x, live=live)
            return jnp.sum(out * ct) + aux, out
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1),
                                          has_aux=True))(p, x)

    (ld, out_d), (gp_d, gx_d) = loss("dense")
    (lg, out_g), (gp_g, gx_g) = loss("grouped")
    np.testing.assert_allclose(out_g, out_d, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(lg, ld, rtol=1e-5)
    np.testing.assert_allclose(gx_g, gx_d, rtol=1e-5, atol=1e-5)
    assert set(gp_g) == set(p) >= {"router", "wi_gate", "wi_up", "wo",
                                   "shared"}
    for name in gp_d:
        for a, b in zip(jax.tree.leaves(gp_g[name]),
                        jax.tree.leaves(gp_d[name])):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5,
                                       err_msg=name)
    if case == "live":
        dead = ~np.asarray(live)
        np.testing.assert_array_equal(
            np.asarray(out_g)[dead],
            np.asarray(moe._with_shared(p, cfg, x, jnp.zeros_like(x)))[dead])
    if case == "skewed":
        _, idx, _ = moe.route(p, cfg, x.reshape(-1, cfg.d_model))
        assert len(np.unique(np.asarray(idx))) == cfg.top_k


# (n_experts, top_k, n_group, topk_group, scale): a Moonlight-like
# router (one group) and a DeepSeek-V3-like one (the group limit binds)
_SIGMOID_CASES = {
    "moonlight": (16, 6, 1, 1, 2.446),
    "v3": (32, 8, 8, 4, 2.5),
}


def _plain_sigmoid_route(logits, bias, k, n_group, topk_group, scale, n_seq,
                         alpha):
    """DeepSeek-V3's router token by token in numpy (arXiv:2412.19437
    §2.1.2 and eq. 17-20): experts, weights by expert, balance term."""
    s = 1.0 / (1.0 + np.exp(-logits))
    T, E = s.shape
    per = E // n_group
    chosen, weights = [], []
    for t in range(T):
        b = s[t] + bias
        worth = [np.sort(b[g * per:(g + 1) * per])[-2:].sum()
                 for g in range(n_group)]
        kept = np.argsort(worth)[::-1][:topk_group]
        allowed = np.concatenate([np.arange(g * per, (g + 1) * per)
                                  for g in kept])
        idx = allowed[np.argsort(b[allowed])[::-1][:k]]
        w = s[t, idx] / s[t, idx].sum() * scale
        chosen.append(idx)
        weights.append(dict(zip(idx.tolist(), w)))
    S = T // n_seq
    aux = 0.0
    for q in range(n_seq):
        rows = slice(q * S, (q + 1) * S)
        f = np.zeros(E)
        for idx in chosen[rows]:
            f[idx] += E / (k * S)
        P = (s[rows] / s[rows].sum(-1, keepdims=True)).mean(0)
        aux += alpha * np.sum(f * P) / n_seq
    return chosen, weights, aux


@pytest.mark.parametrize("case", sorted(_SIGMOID_CASES))
def test_sigmoid_router_matches_plain_router(case):
    E, k, n_group, topk_group, scale = _SIGMOID_CASES[case]
    cfg = ModelConfig(name=case, arch_type="moe", n_layers=1, d_model=32,
                      n_heads=2, n_kv_heads=2, head_dim=16, d_ff=64,
                      vocab_size=64, dtype="float32", n_experts=E, top_k=k,
                      moe_d_ff=12, router_score="sigmoid", n_group=n_group,
                      topk_group=topk_group, routed_scaling_factor=scale,
                      router_aux_coef=1e-4).validate()
    p = moe.init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
    # a bias of the order of the gaps between scores: it reorders
    bias = 0.1 * jax.random.normal(jax.random.PRNGKey(4), (E,))
    p["e_score_correction_bias"] = bias
    x = jax.random.normal(jax.random.PRNGKey(5), (3 * 16, cfg.d_model))
    w, idx, aux = jax.jit(lambda p, x: moe.route(p, cfg, x, n_seq=3))(p, x)
    logits = np.asarray(x, np.float64) @ np.asarray(p["router"], np.float64)
    chosen, weights, want_aux = _plain_sigmoid_route(
        logits, np.asarray(bias, np.float64), k, n_group, topk_group, scale,
        3, 1e-4)
    for t in range(x.shape[0]):
        assert sorted(np.asarray(idx[t]).tolist()) == sorted(
            chosen[t].tolist()), t
        got = dict(zip(np.asarray(idx[t]).tolist(), np.asarray(w[t])))
        for e, v in weights[t].items():
            # float32 sigmoid and sums against float64: a few ulps
            np.testing.assert_allclose(got[e], v, rtol=1e-5)
    np.testing.assert_allclose(aux, want_aux, rtol=1e-5)
    # the bias moved the choice of some token, and no gradient reaches it
    _, idx0, _ = moe.route(dict(p, e_score_correction_bias=jnp.zeros(E)),
                           cfg, x, n_seq=3)
    assert np.any(np.sort(np.asarray(idx0), 1) != np.sort(np.asarray(idx), 1))
    g = jax.grad(lambda p: jnp.sum(moe.route(p, cfg, x, n_seq=3)[0])
                 + moe.route(p, cfg, x, n_seq=3)[2])(p)
    assert not np.any(np.asarray(g["e_score_correction_bias"]))
    assert np.any(np.asarray(g["router"]))


@pytest.mark.parametrize("mesh_kind, use_pallas, path", [
    ("none", False, "grouped"),
    ("one_device", False, "grouped"),
    ("none", True, "grouped"),
])
def test_auto_picks_the_one_device_path(monkeypatch, mesh_kind, use_pallas,
                                        path):
    taken = []
    for name in ("grouped", "dense"):
        monkeypatch.setattr(moe, f"moe_{name}",
                            lambda p, cfg, x, live=None, name=name:
                            taken.append(name) or (x, 0.0))
    cfg = _tiny("qwen", use_pallas=use_pallas)
    mesh = (None if mesh_kind == "none" else
            Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                 ("data", "model")))
    x = jnp.zeros((1, 4, cfg.d_model))
    moe.apply_moe(None, cfg, x, mesh)
    assert taken == [path]


# the sigmoid family also takes latent attention (MLA), as DeepSeek-V3 does
_MLA = dict(attn_type="mla", kv_lora_rank=16, rope_head_dim=8,
            nope_head_dim=16, v_head_dim=16)


@pytest.mark.parametrize("family", ["qwen", "sigmoid"])
def test_use_pallas_keeps_the_training_path(monkeypatch, caplog, family):
    """``use_pallas`` picks neither the attention core nor the one-device
    MoE path: a tiny MoE's loss and gradients agree with it on and off,
    and every MoE call takes ``grouped``.  What differs is the CE loss,
    a vocabulary-streaming kernel (interpret mode here) against XLA's
    whole-row softmax, both float32: hence a relative tolerance of 1e-5
    on the loss and 1e-4 of the largest gradient entry per leaf."""
    cfg = _tiny(family, **(_MLA if family == "sigmoid" else {}))
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                              cfg.vocab_size)
    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, 1)}
    monkeypatch.setattr(moe, "_PATH_LOGGED", set())
    caplog.set_level(logging.INFO, logger=moe.__name__)

    def loss_and_grads(c):
        return jax.value_and_grad(lambda p: M.loss_fn(p, c, batch)[0])(params)

    loss, grads = loss_and_grads(cfg)
    loss_p, grads_p = loss_and_grads(cfg.replace(use_pallas=True))
    np.testing.assert_allclose(float(loss_p), float(loss), rtol=1e-5)
    for g, r in zip(jax.tree.leaves(grads_p), jax.tree.leaves(grads)):
        assert float(jnp.max(jnp.abs(g - r))) <= (
            1e-4 * float(jnp.max(jnp.abs(r))) + 1e-7)
    paths = [r.getMessage() for r in caplog.records
             if r.getMessage().startswith("moe path: ")]
    assert paths and all(m.startswith("moe path: grouped") for m in paths)

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh
from repro.models import moe
from repro.models.config import ModelConfig

# E=3 exercises the expert-padding branch (E_pad=4 on the 2-way axis)
cfg0 = ModelConfig(name="t", arch_type="moe", n_layers=1, d_model=16,
                   n_heads=2, n_kv_heads=2, head_dim=8, d_ff=32,
                   n_experts=3, top_k=2, moe_d_ff=24, vocab_size=64,
                   capacity_factor=2.0,  # dropless here: comparable to dense
                   dtype="float32").validate()
p = moe.init_moe(jax.random.PRNGKey(0), cfg0, jnp.float32)
x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 16))
mesh = Mesh(np.array(jax.devices()).reshape(2, 2), ("data", "model"))

dense, _ = moe.apply_moe(p, cfg0.replace(moe_impl="dense"), x, mesh)
for impl in ("a2a", "replicated_ep"):
    c = cfg0.replace(moe_impl=impl, use_pallas=True)
    out_p, _ = moe.apply_moe(p, c, x, mesh)
    out_x, _ = moe.apply_moe(p, c.replace(use_pallas=False), x, mesh)
    d = float(jnp.abs(out_p - out_x).max())
    assert d < 1e-5, (impl, "pallas vs xla", d)
    # generous capacity -> no drops -> sharded path matches dense
    dd = float(jnp.abs(out_x - dense).max())
    assert dd < 1e-4, (impl, "vs dense", dd)

# gradients flow through the sharded pallas path (the headline bugfix)
c = cfg0.replace(moe_impl="replicated_ep", use_pallas=True)
g = jax.grad(lambda p: jnp.sum(moe.apply_moe(p, c, x, mesh)[0] ** 2))(p)
for name in ("wi_gate", "wi_up", "wo", "router"):
    gn = float(jnp.linalg.norm(g[name]))
    assert np.isfinite(gn) and gn > 0, (name, gn)
print("OK")
"""


def test_sharded_moe_paths_agree_and_train():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src") \
        + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                       capture_output=True, text=True, timeout=590)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "OK" in r.stdout
