"""Every matmul of the compiled programs belongs to a named layer.

The program opens a ``jax.named_scope`` (``repro.utils.scopes``) at each
layer boundary; XLA keeps it in each instruction's ``op_name``, and the
benchmark attributes device time to layers by it
(``bench/harness/scopes.py``).  Here the optimized HLO of a Phase III
epoch (``moe_grouped`` on the XLA path, ``moe_dense`` on the Pallas
path; and of a Moonlight-shaped model: MLA, a dense layer, the sigmoid
router) and of a Phase II distillation epoch with VAA, each on the XLA and
the Pallas path (kernels in interpret mode), and of an
expert-parallel ``moe_a2a`` forward on four CPU devices is compiled, and
every ``dot``/``convolution`` of it, forward and backward, must name a
layer.
"""
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.core import merge, tuning
from repro.core import vaa as vaa_mod
from repro.federated import server
from repro.models import model as M
from repro.optim import adamw_init
from repro.utils import scopes

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_MATMUL = re.compile(r"= [^\n]*? (?:dot|convolution)\([^\n]*")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_WRAPPED = re.compile(r"^[A-Za-z_]\w*\((.*)\)$")

_A2A = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh
from repro.models import moe
from repro.models.config import ModelConfig

cfg = ModelConfig(name="t", arch_type="moe", n_layers=1, d_model=16,
                  n_heads=2, n_kv_heads=2, head_dim=8, d_ff=32, n_experts=3,
                  top_k=2, moe_d_ff=24, n_shared_experts=1, vocab_size=64,
                  moe_impl="a2a", dtype="float32").validate()
p = moe.init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 16))
mesh = Mesh(np.array(jax.devices()).reshape(2, 2), ("data", "model"))
fwd = jax.jit(lambda p, x: moe.apply_moe(p, cfg, x, mesh)[0])
with open(sys.argv[1], "w") as f:
    f.write(fwd.lower(p, x).compile().as_text())
"""


def _layers(op_name):
    """The scope names in one ``op_name``, transformation wrappers
    (``jvp(...)``, ``transpose(...)``) taken off."""
    out = []
    for part in op_name.split("/"):
        m = _WRAPPED.match(part)
        while m:
            part = m.group(1)
            m = _WRAPPED.match(part)
        out.append(part)
    return out


def _reduced_moe(use_pallas):
    return get_config("qwen2-moe-a2.7b", variant="reduced").replace(
        use_pallas=use_pallas)


def _reduced_mla():
    """Moonlight-16B-A3B's block: MLA with a full-rank query, one dense
    layer, then experts under the sigmoid router with its bias."""
    return get_config("deepseek-v3-671b", variant="reduced").replace(
        n_layers=3, q_lora_rank=0, first_dense_layers=1, n_experts=8,
        top_k=3, n_shared_experts=2, n_group=1, topk_group=1, n_mtp=0,
        routed_scaling_factor=2.446, norm_eps=1e-5, remat_attn_chunks=True)


def _tune_hlo(cfg):
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    mask, opt = tuning.init_tuning(params)
    tok = jnp.zeros((1, 2, 32), jnp.int32)
    epoch = server._tune_epoch_fn(cfg, None, mask, 1, 1e-3, 0)
    return epoch.lower(params, opt, {"tokens": tok, "labels": tok}
                       ).compile().as_text()


def _distill_hlo(use_pallas):
    s_cfg = merge.base_config_of(_reduced_moe(use_pallas))
    t_cfg = get_config("gpt2", variant="reduced").replace(
        vocab_size=s_cfg.vocab_size, use_pallas=False)
    n_stages, heads, p_q = 2, 4, 32
    trainable = {
        "student": M.init_params(jax.random.PRNGKey(0), s_cfg),
        "vaa": vaa_mod.init_vaa(jax.random.PRNGKey(1), n_stages=n_stages,
                                d_student=s_cfg.d_model,
                                d_teacher=t_cfg.d_model, d=64,
                                n_heads=heads, p_q=p_q)}
    opt = adamw_init(trainable)
    t_params = M.init_params(jax.random.PRNGKey(2), t_cfg)
    tok = jnp.zeros((1, 2, 32), jnp.int32)
    epoch = server._distill_epoch_fn(s_cfg, t_cfg, 1.0, 1.0, 2.0, n_stages,
                                     heads, p_q, 1, 1e-3, 0, None)
    return epoch.lower(trainable, opt, t_params,
                       {"tokens": tok, "labels": tok}).compile().as_text()


def _a2a_hlo(tmp_path):
    out = tmp_path / "a2a.hlo"
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src") + os.pathsep \
        + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _A2A, str(out)], env=env,
                       capture_output=True, text=True, timeout=590)
    assert r.returncode == 0, r.stderr
    return out.read_text()


TUNE = {scopes.ATTENTION, scopes.ROUTER, scopes.EXPERTS,
        scopes.SHARED_EXPERT, scopes.HEAD}
DISTILL = {scopes.ATTENTION, scopes.MLP, scopes.VAA, scopes.KD_LOSS}
# program -> (what compiles it, the layers its matmuls' scopes must show)
PROGRAMS = {
    "tune_xla": (lambda tmp: _tune_hlo(_reduced_moe(False)),
                 TUNE | {scopes.FFN}),
    "tune_pallas": (lambda tmp: _tune_hlo(_reduced_moe(True)), TUNE),
    "tune_mla_xla": (lambda tmp: _tune_hlo(_reduced_mla()),
                     TUNE | {scopes.FFN, scopes.MLP}),
    "distill_xla": (lambda tmp: _distill_hlo(False), DISTILL),
    "distill_pallas": (lambda tmp: _distill_hlo(True), DISTILL),
    "moe_a2a_4dev": (_a2a_hlo, {scopes.ROUTER, scopes.EXPERTS, scopes.FFN,
                                scopes.SHARED_EXPERT}),
}


@pytest.fixture(scope="module")
def hlo(tmp_path_factory):
    cache = {}

    def get(name):
        if name not in cache:
            cache[name] = PROGRAMS[name][0](tmp_path_factory.mktemp(name))
        return cache[name]
    return get


@pytest.mark.parametrize("program", sorted(PROGRAMS))
def test_every_matmul_names_a_layer(hlo, program):
    found, seen_backward = set(), False
    matmuls = _MATMUL.findall(hlo(program))
    assert matmuls
    for line in matmuls:
        m = _OP_NAME.search(line)
        assert m, f"no op_name: {line[:200]}"
        parts = _layers(m.group(1))
        layers = [p for p in parts if p in scopes.LAYERS]
        # a layer scan holds no matmul of its own: one whose innermost
        # layer is the scan fell outside every layer's scope
        assert layers and layers[-1] != scopes.STACK, m.group(1)
        found |= set(parts) & (set(scopes.LAYERS)
                               | set(scopes.SUBSCOPES[scopes.EXPERTS]))
        seen_backward |= m.group(1).count("transpose(") > 0
    assert PROGRAMS[program][1] <= found, PROGRAMS[program][1] - found
    assert seen_backward == (program != "moe_a2a_4dev")


def test_expert_parallel_path_names_its_stages(hlo):
    text = hlo("moe_a2a_4dev")
    stages = set()
    for m in _OP_NAME.finditer(text):
        parts = _layers(m.group(1))
        if scopes.EXPERTS in parts:
            inner = parts[parts.index(scopes.EXPERTS) + 1:]
            stages |= set(inner) & set(scopes.SUBSCOPES[scopes.EXPERTS])
    assert stages == {scopes.DISPATCH, scopes.FFN, scopes.COMBINE}
    assert re.search(r"all-to-all[^\n]*op_name=\"[^\"]*/dispatch/", text)
    assert re.search(r"all-to-all[^\n]*op_name=\"[^\"]*/combine/", text)


def test_benchmark_keeps_the_programs_layer_names():
    sys.path.insert(0, os.path.join(ROOT, "bench"))
    try:
        from harness import scopes as bench_scopes
    finally:
        sys.path.remove(os.path.join(ROOT, "bench"))
    assert bench_scopes.LAYERS == scopes.LAYERS
    assert bench_scopes.SUBSCOPES == scopes.SUBSCOPES
