"""Each per-layer metric's reader: nothing where it finds nothing to
read, and no share of the peak above 100% on work the program cannot
do faster than the chip's peak."""
import json
import os

import pytest

from conftest import BENCH
from harness.spec import load_module
from reference.qwen2_moe import arch

with open(os.path.join(BENCH, "configs", "qwen1.5-moe-a2.7b.tune.json")) as f:
    A = arch(json.load(f))
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
WORK = {"arch": A, "batch": 2, "seq": 2048, "train": True, "teacher": None}


def _ctx(kernels, steps=10, elapsed=10.0, busy=9.0):
    return {"window": {"steps": steps, "elapsed_s": elapsed,
                       "end_to_end": {"train_tokens_per_s": 1.0}},
            "trace": {"kernels": kernels, "busy_s": busy, "window_s": 10.0},
            "work": WORK, "flops": "moe_lm_train", "chips": 1,
            "peaks": PEAKS}


@pytest.mark.parametrize("name", ["step_mfu.train", "idle_share.train"])
def test_training_readers_are_silent_outside_training(name):
    ctx = _ctx({})
    ctx["window"]["end_to_end"] = {"ttft_p95_ms": 1.0}
    assert load_module("metrics", name).read(ctx) is None


def test_step_mfu_and_idle_share():
    ctx = _ctx({}, steps=20, elapsed=10.0, busy=9.5)
    flops = load_module("flops", "moe_lm_train").step_flops(A, 2, 2048)
    mfu = load_module("metrics", "step_mfu.train").read(ctx)
    assert mfu == pytest.approx(100 * flops * 2.0 / 197e12)
    assert 0 < mfu < 100
    idle = load_module("metrics", "idle_share.train").read(ctx)
    assert idle == pytest.approx(5.0)
