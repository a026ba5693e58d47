"""Attribution of device operations to the program's layers: the
``op_name`` parser, the instruction map on HLO snippets, the seconds on
the recorded v5e trace, the per-layer FLOP groups and the per-layer
shares of ``layers.py``."""
import importlib.util
import json
import os
import subprocess
import sys

import jax
import pytest

from conftest import BENCH, ROOT
from harness import scopes, trace
from harness.spec import load_module
from reference.qwen2_moe import arch

HERE = os.path.join(os.path.dirname(__file__), "data")
with open(os.path.join(HERE, "tiny.kernels.json")) as f:
    KERNELS = json.load(f)
with open(os.path.join(BENCH, "configs", "qwen1.5-moe-a2.7b.tune.json")) as f:
    A = arch(json.load(f))


@pytest.mark.parametrize("op_name,scope", [
    ("jit(epoch)/while/body/closed_call/transpose(jvp(stack))/while/body/"
     "closed_call/checkpoint/attention/dot_general", "attention"),
    ("jit(epoch)/jvp(stack)/while/body/closed_call/experts/"
     "td,edf->etf/dot_general", "experts"),
    ("jit(f)/transpose(jvp(head))/while/body/checkpoint/"
     "rematted_computation/dot_general", "head"),
    ("jit(f)/stack/while/body/experts/shard_map/dispatch/all_to_all",
     "experts/dispatch"),
    ("jit(f)/experts/ffn/router/dot_general", "router"),
    ("jit(f)/stack/while/body/dynamic_slice", "stack"),
    ("jit(f)/ffn/dot_general", "unscoped"),
    ("jit(f)/while/body/add", "unscoped"),
])
def test_innermost_layer_wins_through_wrappers(op_name, scope):
    assert scopes.scope_of(op_name) == scope


HLO = """%fused_computation.1 (p0: bf16[4,8], p1: bf16[8,2]) -> bf16[4,2] {
  %p0 = bf16[4,8]{1,0} parameter(0)
  %p1 = bf16[8,2]{1,0} parameter(1)
  %convolution.1 = bf16[4,2]{1,0} convolution(%p0, %p1), dim_labels=bf_io->bf, metadata={op_name="jit(f)/jvp(stack)/while/body/experts/dot_general"}
  ROOT %add.1 = bf16[4,2]{1,0} add(%convolution.1, %convolution.1), metadata={op_name="jit(f)/jvp(stack)/while/body/add"}
}

%fused_computation.2 (p0: f32[4]) -> f32[4] {
  %p0 = f32[4]{0} parameter(0)
  ROOT %multiply.2 = f32[4]{0} multiply(%p0, %p0), metadata={op_name="jit(f)/norm/mul"}
}

%fused_computation.3 (p0: f32[4]) -> f32[4] {
  %p0 = f32[4]{0} parameter(0)
  ROOT %fusion.9 = f32[4]{0} fusion(%p0), kind=kLoop, calls=%fused_computation.2
}

ENTRY %main (a: bf16[4,8], b: bf16[8,2], c: f32[4]) -> bf16[4,2] {
  %a = bf16[4,8]{1,0} parameter(0)
  %b = bf16[8,2]{1,0} parameter(1)
  %c = f32[4]{0} parameter(2)
  %fusion.1 = bf16[4,2]{1,0} fusion(%a, %b), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(f)/jvp(stack)/while/body/add"}
  %fusion.2 = f32[4]{0} fusion(%c), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(f)/optimizer/mul"}
  %fusion.3 = f32[4]{0} fusion(%c), kind=kLoop, calls=%fused_computation.3
  %copy.4 = f32[4]{0} copy(%c)
  ROOT %custom-call.5 = bf16[4,2]{1,0} custom-call(%fusion.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/head/pallas_call"}
}
"""


def test_instructions_take_their_layer():
    got = scopes.instruction_scopes([HLO])
    assert got["fusion.1"] == "experts"          # its heaviest op's
    assert got["fusion.2"] == "optimizer"        # no heavy op: its own
    assert got["fusion.3"] == "norm"             # nested fusion opened
    assert got["copy.4"] == "unscoped"           # no metadata
    assert got["custom-call.5"] == "head"


def test_seconds_on_a_recorded_trace():
    """Every operation's self time lands in exactly one scope."""
    pd = jax.profiler.ProfileData.from_file(os.path.join(HERE,
                                                         "tiny.xplane.pb"))
    red = trace.reduce_profile(pd, "bench.window", 1, KERNELS)
    total = sum(red["kernels"].values())
    bare = scopes.seconds(pd, "bench.window", 1, [])
    assert bare == {"unscoped": pytest.approx(total, rel=1e-12)}
    text = ('  %grouped_ffn.1 = bf16[4,256,512]{2,1,0} custom-call(%x.1), '
            'custom_call_target="tpu_custom_call", metadata={op_name='
            '"jit(f)/experts/ffn/pallas_call"}\n'
            '  %_lambda_.1 = f32[] custom-call(%h.1), custom_call_target='
            '"tpu_custom_call", metadata={op_name="jit(f)/kd_loss/'
            'pallas_call"}\n')
    got = scopes.seconds(pd, "bench.window", 1,
                         [f"ENTRY %main () -> f32[] {{\n{text}}}\n"])
    assert got["experts/ffn"] == red["kernels"]["_ffn_kernel"]
    assert got["kd_loss"] == red["kernels"]["_kd_kernel"]
    assert sum(got.values()) == pytest.approx(total, rel=1e-12)
    assert scopes.by_layer(got)["experts"] == got["experts/ffn"]


def test_layer_flops_sum_to_the_step():
    layers = load_module("flops", "moe_lm_layers")
    step = load_module("flops", "moe_lm_train")
    got = layers.layer_flops(A, 2, 2048)
    assert sum(got.values()) == step.step_flops(A, 2, 2048)
    assert set(got) <= set(scopes.LAYERS)
    # frozen experts: forward and input gradient of tokens x top_k rows
    assert got["experts"] == 2 * A["n_moe"] * 6 * 4096 * A["k"] * A["D"] \
        * A["F"]
    assert got["head"] == 3 * 2 * 4096 * A["D"] * A["V"]


def test_layer_shares():
    spec = importlib.util.spec_from_file_location(
        "bench_layers", os.path.join(BENCH, "layers.py"))
    layers_py = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(layers_py)
    flops = load_module("flops", "moe_lm_layers").layer_flops(A, 2, 2048)
    scope_s = {"attention": 2.0, "experts": 4.0, "experts/ffn": 4.0,
               "shared_expert": 1.0, "stack": 1.0, "unscoped": 0.5}
    got = layers_py.summary({"steps": 10}, scope_s, 12.5,
                            {"arch": A, "batch": 2, "seq": 2048},
                            {"bf16_flops": 197e12}, 1)
    assert got["mfu"]["experts"] == pytest.approx(
        100 * flops["experts"] * 10 / (8.0 * 197e12))
    assert got["mfu"]["attention"] == pytest.approx(
        100 * flops["attention"] * 10 / (2.0 * 197e12))
    assert "head" not in got["mfu"]        # no time of its own: no share
    assert got["stack_share"] == pytest.approx(8.0)
    assert got["unscoped_share"] == pytest.approx(4.0)


def test_layers_needs_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(BENCH, "layers.py"),
                        "--workload", "qwen2moe.tune.xla", "--seed", "1",
                        "--seconds", "1"],
                       capture_output=True, text=True, env=env, cwd=ROOT,
                       timeout=300)
    assert p.returncode == 2 and p.stdout == ""
    assert "needs a TPU" in p.stderr
