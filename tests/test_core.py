"""DeepFusion core invariants: clustering, proxies, VAA, merge, tuning."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import clustering, merge, proxy, tuning
from repro.core import vaa as vaa_mod
from repro.core.distill import select_stages
from repro.models import model as M
from repro.models.config import ModelConfig
from repro.optim import adamw_init, adamw_update, global_norm_clip
from repro.utils.pytree import tree_average

SMALL = dict(vocab_size=128, dtype="float32", remat=False,
             attn_chunk_q=16, attn_chunk_k=16, loss_chunk=16)


def dense_cfg(**kw):
    base = dict(name="d", n_layers=2, d_model=32, n_heads=2, n_kv_heads=2,
                head_dim=16, d_ff=64, **SMALL)
    base.update(kw)
    return ModelConfig(**base).validate()


def moe_cfg(**kw):
    base = dict(name="m", arch_type="moe", n_layers=2, d_model=32, n_heads=2,
                n_kv_heads=2, head_dim=16, d_ff=64, n_experts=3, top_k=2,
                moe_d_ff=64, n_shared_experts=1, **SMALL)
    base.update(kw)
    return ModelConfig(**base).validate()


# ---------------------------------------------------------------------------
# Phase I
# ---------------------------------------------------------------------------

def test_similarity_matrix_is_cosine():
    e = np.random.default_rng(0).standard_normal((5, 8)).astype(np.float32)
    sim = clustering.cosine_similarity_matrix(e)
    assert sim.shape == (5, 5)
    np.testing.assert_allclose(np.diag(sim), 1.0, rtol=1e-5)
    assert np.all(sim <= 1.0 + 1e-6)


def test_kmeans_recovers_separated_clusters():
    rng = np.random.default_rng(0)
    centers = np.eye(4, 16, dtype=np.float32)
    e = np.concatenate([centers[i] + 0.01 * rng.standard_normal((10, 16))
                        for i in range(4)]).astype(np.float32)
    labels, _ = clustering.spherical_kmeans(e, 4, seed=0)
    for i in range(4):
        grp = labels[i * 10:(i + 1) * 10]
        assert len(set(grp.tolist())) == 1  # pure clusters
    assert len(set(labels.tolist())) == 4


def test_arch_constrained_clustering():
    rng = np.random.default_rng(1)
    e = rng.standard_normal((12, 8)).astype(np.float32)
    arch = [0, 1] * 6
    res = clustering.cluster_devices(e, 4, arch_ids=arch, seed=0)
    for members in res.members:
        archs = {arch[m] for m in members}
        assert len(archs) <= 1


def test_proxy_is_weight_average():
    cfg = dense_cfg()
    p1 = M.init_params(jax.random.PRNGKey(0), cfg)
    p2 = M.init_params(jax.random.PRNGKey(1), cfg)
    res = clustering.ClusterResult(
        labels=np.array([0, 0]), centroids=np.zeros((1, 4)),
        similarity=np.ones((2, 2)), members=[[0, 1]])
    proxies = proxy.build_proxies([p1, p2], res, [0, 0])
    assert len(proxies) == 1
    avg = tree_average([p1, p2])
    for a, b in zip(jax.tree.leaves(proxies[0]["params"]),
                    jax.tree.leaves(avg)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)


def test_proxy_rejects_mixed_arch_cluster():
    cfg = dense_cfg()
    p1 = M.init_params(jax.random.PRNGKey(0), cfg)
    res = clustering.ClusterResult(
        labels=np.array([0, 0]), centroids=np.zeros((1, 4)),
        similarity=np.ones((2, 2)), members=[[0, 1]])
    with pytest.raises(AssertionError):
        proxy.build_proxies([p1, p1], res, [0, 1])


# ---------------------------------------------------------------------------
# Phase II: VAA
# ---------------------------------------------------------------------------

def test_vaa_shapes_and_grads():
    J, B, S, dS, dT, d, pq = 3, 2, 24, 32, 48, 16, 12
    key = jax.random.PRNGKey(0)
    params = vaa_mod.init_vaa(key, n_stages=J, d_student=dS, d_teacher=dT,
                              d=d, n_heads=2, p_q=pq)
    stages = [jax.random.normal(jax.random.PRNGKey(i), (B, S, dS))
              for i in range(J)]
    outs = vaa_mod.vaa_apply(params, stages, n_heads=2, p_q=pq)
    assert len(outs) == J
    for o in outs:
        assert o.shape == (B, pq // J, dT)
    t_stages = [jax.random.normal(jax.random.PRNGKey(10 + i), (B, S, dT))
                for i in range(J)]
    loss = vaa_mod.feature_matching_loss(params, stages, t_stages,
                                         n_heads=2, p_q=pq)
    assert jnp.isfinite(loss) and loss >= 0
    g = jax.grad(lambda p: vaa_mod.feature_matching_loss(
        p, stages, t_stages, n_heads=2, p_q=pq))(params)
    gn = sum(float(jnp.sum(jnp.abs(x))) for x in jax.tree.leaves(g))
    assert np.isfinite(gn) and gn > 0


def test_vaa_attention_mixes_stages():
    """Blended stage j must depend on OTHER stages' features (the view
    alignment property) — zeroing stage 0 changes stage 2's output."""
    J, B, S, dS, dT, pq = 3, 1, 8, 16, 16, 6
    params = vaa_mod.init_vaa(jax.random.PRNGKey(0), n_stages=J,
                              d_student=dS, d_teacher=dT, d=8, n_heads=2,
                              p_q=pq)
    stages = [jax.random.normal(jax.random.PRNGKey(i), (B, S, dS))
              for i in range(J)]
    out_a = vaa_mod.vaa_apply(params, stages, n_heads=2, p_q=pq)
    stages_b = [jnp.zeros_like(stages[0])] + stages[1:]
    out_b = vaa_mod.vaa_apply(params, stages_b, n_heads=2, p_q=pq)
    assert float(jnp.max(jnp.abs(out_a[2] - out_b[2]))) > 1e-6


def test_vaa_short_sequence_pads_to_full_patches():
    """Regression: S < P_q/J used to yield min(P, S) patches, misaligning
    the per-stage slices of the concatenated query block and breaking
    L_FM shapes.  patchify must always return exactly P patches."""
    J, B, S, dS, dT, pq = 4, 2, 8, 16, 24, 64
    P = pq // J  # 16 > S
    params = vaa_mod.init_vaa(jax.random.PRNGKey(0), n_stages=J,
                              d_student=dS, d_teacher=dT, d=16, n_heads=2,
                              p_q=pq)
    stages = [jax.random.normal(jax.random.PRNGKey(i), (B, S, dS))
              for i in range(J)]
    assert vaa_mod.patchify(stages[0], P).shape == (B, P, dS)
    outs = vaa_mod.vaa_apply(params, stages, n_heads=2, p_q=pq)
    assert len(outs) == J
    for o in outs:
        assert o.shape == (B, P, dT)
        assert bool(jnp.all(jnp.isfinite(o)))
    t_stages = [jax.random.normal(jax.random.PRNGKey(10 + i), (B, S, dT))
                for i in range(J)]
    loss = vaa_mod.feature_matching_loss(params, stages, t_stages,
                                         n_heads=2, p_q=pq)
    assert jnp.isfinite(loss) and loss >= 0
    g = jax.grad(lambda p: vaa_mod.feature_matching_loss(
        p, stages, t_stages, n_heads=2, p_q=pq))(params)
    gn = sum(float(jnp.sum(jnp.abs(x))) for x in jax.tree.leaves(g))
    assert np.isfinite(gn) and gn > 0


def test_patchify_preserves_mean():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 8))
    p = vaa_mod.patchify(x, 4)
    assert p.shape == (2, 4, 8)
    np.testing.assert_allclose(np.asarray(p[:, 0]),
                               np.asarray(x[:, :4].mean(1)), rtol=1e-5)


def test_select_stages_even_spacing():
    stages = jnp.arange(10)[:, None, None, None] * jnp.ones((10, 1, 2, 3))
    sel = select_stages(stages, 4)
    assert len(sel) == 4
    assert float(sel[-1][0, 0, 0]) == 9.0  # last stage always included


# ---------------------------------------------------------------------------
# Phase III: merge + tuning
# ---------------------------------------------------------------------------

def test_merge_rule_expert_copy_and_average():
    mcfg = moe_cfg()
    bcfg = merge.base_config_of(mcfg)
    assert bcfg.d_ff == mcfg.moe_d_ff
    bases = [M.init_params(jax.random.PRNGKey(i), bcfg) for i in range(3)]
    moe_params = merge.merge_into_moe(jax.random.PRNGKey(9), mcfg, bases)
    # Eq. 12: expert e FFN == base e FFN
    for e in range(3):
        np.testing.assert_allclose(
            np.asarray(moe_params["blocks"]["sub0"]["moe"]["wi_gate"][:, e]),
            np.asarray(bases[e]["blocks"]["sub0"]["mlp"]["wi_gate"]),
            rtol=1e-6)
    # Eq. 13: embedding == average of base embeddings
    avg_embed = sum(np.asarray(b["embed"], np.float64) for b in bases) / 3
    np.testing.assert_allclose(np.asarray(moe_params["embed"]), avg_embed,
                               rtol=1e-5, atol=1e-6)
    # attention weights averaged
    avg_wq = sum(np.asarray(b["blocks"]["sub0"]["attn"]["wq"], np.float64)
                 for b in bases) / 3
    np.testing.assert_allclose(
        np.asarray(moe_params["blocks"]["sub0"]["attn"]["wq"]), avg_wq,
        rtol=1e-5, atol=1e-6)
    # merged model must run
    toks = jax.random.randint(jax.random.PRNGKey(3), (2, 16), 0,
                              mcfg.vocab_size)
    loss, _ = M.loss_fn(moe_params, mcfg,
                        {"tokens": toks, "labels": toks})
    assert bool(jnp.isfinite(loss))


def test_merge_round_robin_when_fewer_bases():
    mcfg = moe_cfg()
    bcfg = merge.base_config_of(mcfg)
    bases = [M.init_params(jax.random.PRNGKey(i), bcfg) for i in range(2)]
    moe_params = merge.merge_into_moe(jax.random.PRNGKey(9), mcfg, bases)
    np.testing.assert_allclose(  # expert 2 <- base 0 (round robin)
        np.asarray(moe_params["blocks"]["sub0"]["moe"]["wo"][:, 2]),
        np.asarray(bases[0]["blocks"]["sub0"]["mlp"]["wo"]), rtol=1e-6)


def test_freeze_mask_freezes_experts_only():
    mcfg = moe_cfg()
    params = M.init_params(jax.random.PRNGKey(0), mcfg)
    mask = tuning.expert_freeze_mask(params)
    flat, _ = jax.tree_util.tree_flatten_with_path(mask)
    from repro.utils.pytree import path_str
    for pth, m in flat:
        p = path_str(pth)
        if "moe/wi_gate" in p or "moe/wi_up" in p or "moe/wo" in p \
           or "moe/shared/" in p:
            assert m is False, p
        else:
            assert m is True, p
    frac = tuning.trainable_fraction(params)
    assert 0 < frac < 1


def test_frozen_experts_unchanged_by_tuning_step():
    mcfg = moe_cfg()
    params = M.init_params(jax.random.PRNGKey(0), mcfg)
    mask, opt = tuning.init_tuning(params)
    step = tuning.make_tune_step(mcfg, mask)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                              mcfg.vocab_size)
    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, 1)}
    new_params, opt, loss, _ = step(params, opt, batch, 1e-2)
    before = params["blocks"]["sub0"]["moe"]["wi_gate"]
    after = new_params["blocks"]["sub0"]["moe"]["wi_gate"]
    np.testing.assert_array_equal(np.asarray(before), np.asarray(after))
    # but the router DID move
    assert float(jnp.max(jnp.abs(
        params["blocks"]["sub0"]["moe"]["router"]
        - new_params["blocks"]["sub0"]["moe"]["router"]))) > 0
    # and frozen moments are scalar (memory claim of §IV.D)
    assert opt["m"]["blocks"]["sub0"]["moe"]["wi_gate"].shape == ()


# the DeepSeek-V3 block at CPU size: MLA with a full-rank query, one
# leading dense layer, the sigmoid router with its correction bias
V3_TINY = dict(name="v3", n_layers=3, attn_type="mla", q_lora_rank=0,
               kv_lora_rank=16, rope_head_dim=8, nope_head_dim=16,
               v_head_dim=16, head_dim=24, n_experts=8, top_k=3,
               n_shared_experts=2, moe_d_ff=32, first_dense_layers=1,
               router_score="sigmoid", n_group=2, topk_group=1,
               routed_scaling_factor=2.5)


@pytest.mark.parametrize("family", ["qwen2_moe", "deepseek_v3"])
def test_tune_step_matches_full_gradient_step(family):
    """The tapped step, which differentiates only the trainable leaves
    and takes each frozen leaf's squared gradient norm inside the layer
    scan, against a step written here: ``jax.grad`` over every leaf,
    then ``global_norm_clip`` over all of them.  The clip engages, so
    the frozen share of the norm sets every trainable update."""
    cfg = moe_cfg(n_layers=3, n_experts=4) if family == "qwen2_moe" \
        else moe_cfg(**V3_TINY)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    if family == "deepseek_v3":   # a correction bias that moves routing
        params = jax.tree_util.tree_map_with_path(
            lambda p, x: 0.1 * jax.random.normal(jax.random.PRNGKey(5),
                                                 x.shape)
            if "e_score_correction_bias" in str(p) else x, params)
    mask, opt = tuning.init_tuning(params)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                              cfg.vocab_size)
    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, 1)}
    lr, clip = 1e-2, 1.0

    def full_step(params, opt):
        (loss, _), g = jax.value_and_grad(
            lambda p: M.loss_fn(p, cfg, batch), has_aux=True)(params)
        train_norm = jnp.sqrt(sum(
            jnp.sum(jnp.square(x)) for x, m in
            zip(jax.tree.leaves(g), jax.tree.leaves(mask)) if m))
        g, gnorm = global_norm_clip(g, clip)
        new, opt, _ = adamw_update(g, opt, params, lr=lr,
                                   weight_decay=0.01, freeze_mask=mask,
                                   clip_norm=0.0)
        return new, opt, loss, gnorm, train_norm

    want_p, _, want_loss, want_norm, train_norm = jax.jit(full_step)(
        params, opt)
    got_p, got_o, loss, metrics = jax.jit(
        tuning.make_tune_step(cfg, mask))(params, opt, batch, lr)

    np.testing.assert_array_equal(np.asarray(loss), np.asarray(want_loss))
    np.testing.assert_allclose(float(metrics["grad_norm"]),
                               float(want_norm), rtol=1e-6)
    assert float(want_norm) > clip                 # the clip engages
    assert float(train_norm) < 0.999 * float(want_norm)  # frozen share
    flat = zip(jax.tree.leaves(params), jax.tree.leaves(got_p),
               jax.tree.leaves(want_p), jax.tree.leaves(mask),
               jax.tree.leaves(got_o["m"]), jax.tree.leaves(got_o["v"]))
    for before, got, want, trainable, m, v in flat:
        if trainable:
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=0, atol=1e-6)
        else:
            np.testing.assert_array_equal(np.asarray(got),
                                          np.asarray(before))
            assert m.shape == v.shape == ()
