"""The plain reference's own machinery."""
import jax
import jax.numpy as jnp
import numpy as np

from reference import moe_lm


def test_tap_counts_the_frozen_gradient_norm():
    w = jax.random.normal(jax.random.PRNGKey(0), (3, 4))
    x = jax.random.normal(jax.random.PRNGKey(1), (5, 3))

    def f(w):
        return jnp.sum(jnp.sin(x @ w))

    want = jnp.sum(jnp.square(jax.grad(f)(w)))
    got = jax.grad(lambda p: f(moe_lm.tap(w, p)))(jnp.zeros(()))
    np.testing.assert_allclose(got, want, rtol=1e-6)


def test_unstack_splits_layer_groups_only():
    p = {"embed": jnp.zeros((4, 2)), "blocks/sub0/attn/wq": jnp.ones((3, 2, 2))}
    u = moe_lm.unstack(p)
    assert sorted(u) == ["blocks/sub0/attn/wq#0", "blocks/sub0/attn/wq#1",
                         "blocks/sub0/attn/wq#2", "embed"]
    assert moe_lm.leaf_path("blocks/sub0/attn/wq#2") == "blocks/sub0/attn/wq"


def test_frozen_is_the_expert_ffns():
    assert moe_lm.frozen("blocks/sub0/moe/wi_gate")
    assert moe_lm.frozen("blocks/sub0/moe/shared/wo")
    assert not moe_lm.frozen("blocks/sub0/moe/router")
    assert not moe_lm.frozen("dense_blocks/sub0/mlp/wo")
    assert not moe_lm.frozen("lm_head")


def test_calibrate_knows_every_fault():
    import calibrate
    import faults
    assert calibrate.FAULTS == faults.NAMES
