"""The trace reduction, on a small trace recorded on a TPU v5e: three
rounds of a grouped expert FFN (``_ffn_kernel``) and a fused CE head
(``_kd_kernel``), each round a ``bench.dispatch`` and a ``bench.wait``
span inside one ``bench.window`` span."""
import json
import os

import jax
import pytest

from harness import trace

HERE = os.path.join(os.path.dirname(__file__), "data")
DATA = os.path.join(HERE, "tiny.xplane.pb")
# instruction -> kernel, as ``trace.kernel_names`` reads it from the two
# programs compiled for a v5e
with open(os.path.join(HERE, "tiny.kernels.json")) as f:
    KERNELS = json.load(f)


@pytest.fixture(scope="module")
def reduced():
    pd = jax.profiler.ProfileData.from_file(DATA)
    return trace.reduce_profile(pd, "bench.window", 1, KERNELS)


def test_busy_is_inside_the_window(reduced):
    assert 0.0 < reduced["busy_s"] < reduced["window_s"]


def test_kernels_are_found_by_name(reduced):
    k = reduced["kernels"]
    assert k["_ffn_kernel"] > 0.0 and k["_kd_kernel"] > 0.0
    assert sum(k.values()) >= reduced["busy_s"] * 0.999


def test_breakdown_lists(reduced):
    ops = reduced["device_ops"]
    assert 0 < len(ops) <= trace.TOP
    assert ops == sorted(ops, key=lambda o: -o[1])
    gaps = reduced["idle_gaps"]
    assert 0 < len(gaps) <= trace.TOP
    assert all(g[0].startswith("bench.") or g[0] == "no benchmark span"
               for g in gaps)
    assert sum(g[1] for g in gaps) <= reduced["window_s"] - reduced["busy_s"] + 1e-9


def test_window_span_must_be_there():
    pd = jax.profiler.ProfileData.from_file(DATA)
    with pytest.raises(ValueError):
        trace.reduce_profile(pd, "bench.no_such_span")


def test_op_names():
    assert trace.op_name("%grouped_ffn.1 = bf16[4] custom-call(...)",
                         KERNELS) == "_ffn_kernel"
    assert trace.op_name("%fusion.12 = f32[] fusion(...)", {}) == "fusion"


def test_kernel_names_read_the_body():
    import base64
    body = base64.b64encode(b"module { func @_paged_kernel() }").decode()
    text = (f'  %my_call.3 = f32[2] custom-call(%a), custom_call_target='
            f'"tpu_custom_call", backend_config={{"custom_call_config":'
            f'{{"body":"{body}","x":1}}}}')
    assert trace.kernel_names([text]) == {"my_call.3": "_paged_kernel"}


def test_self_time_leaves_out_nested_events():
    evs = [(0, 10, "while"), (1, 4, "fusion"), (5, 9, "while"),
           (6, 8, "_ffn_kernel"), (12, 13, "copy")]
    assert trace.self_times(evs) == {"while": 10 - 3 - 4 + 4 - 2,
                                     "fusion": 3, "_ffn_kernel": 2,
                                     "copy": 1}


def test_fusions_are_labelled_by_their_heaviest_op():
    text = """%fused_computation.5 (p0: bf16[4,8], p1: bf16[8,2]) -> bf16[4,2] {
  %p0 = bf16[4,8]{1,0} parameter(0)
  %p1 = bf16[8,2]{1,0} parameter(1)
  ROOT %convolution.1 = bf16[4,2]{1,0} convolution(%p0, %p1), dim_labels=bf_io->bf
}

ENTRY %main (a: bf16[4,8], b: bf16[8,2]) -> bf16[4,2] {
  %a = bf16[4,8]{1,0} parameter(0)
  %b = bf16[8,2]{1,0} parameter(1)
  ROOT %fusion.5 = bf16[4,2]{1,0} fusion(%a, %b), kind=kOutput, calls=%fused_computation.5
}
"""
    assert trace.op_labels([text]) == {"fusion.5": "fusion:convolution"}


def test_union_merges_overlaps():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
