"""The cell ``moonlight.tune.xla`` at CPU size: ``correct`` holds for the
program and comes out false for the control and for each fault; the MLA
FLOP count on shapes worked by hand; the expert-load reader."""
import copy
import math
import time

import jax
import pytest

import calibrate
import faults
from harness import cell as cell_mod
from harness import compare
from harness.spans import Spans
from harness.spec import load_module
from reference import mla_moe_lm

SEED = 2 ** 31 + 54321
# large enough that bfloat16's noise in the program's readings stays
# under the cell's limits, which were read at full size
TINY_MOONLIGHT = {
    "hidden_size": 128, "intermediate_size": 96, "moe_intermediate_size": 32,
    "n_routed_experts": 8, "num_experts_per_tok": 3, "n_shared_experts": 2,
    "num_hidden_layers": 3, "num_attention_heads": 4,
    "num_key_value_heads": 4, "kv_lora_rank": 32, "qk_nope_head_dim": 32,
    "qk_rope_head_dim": 16, "v_head_dim": 32, "vocab_size": 512,
}
TINY_TRAFFIC = {"seq_len": 256, "batch": 4, "pool": 4}


@pytest.fixture
def tiny_moonlight():
    """The cell ``moonlight.tune.xla`` at CPU size: its own job, traffic
    mix, limits and configuration family, with small widths."""
    from harness import spec
    cell = copy.deepcopy(spec.load_cell("moonlight.tune.xla"))
    cell.config.update(TINY_MOONLIGHT)
    cell.traffic.update(TINY_TRAFFIC)
    return cell


def _run(cell):
    return cell_mod.run(cell, SEED, 0.5, False, t_start=time.time(),
                        devices=jax.devices())


def test_program_is_correct(tiny_moonlight, cpu_peaks):
    out = _run(tiny_moonlight)
    line = out["line"]
    assert line["correct"], out["stderr"]
    assert line["failed"] == 0 and line["attempted"] >= 2
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}


def test_window_counts_the_expert_load(tiny_moonlight, cpu_peaks):
    """The window carries a count, taken when called, of one row of
    per-expert counts per expert layer, each summing to the batch's
    assignments, and the reader takes the busiest expert over the mean."""
    job = load_module("jobs", "tune_mla").Job(tiny_moonlight, SEED, Spans())
    win = job.window(0.2)
    rows = win["expert_load"]()
    T = TINY_TRAFFIC["batch"] * TINY_TRAFFIC["seq_len"]
    assert len(rows) == 2 and all(len(r) == 8 for r in rows)
    assert all(sum(r) == T * 3 for r in rows)
    load = load_module("metrics", "expert_load.train").read(
        {"window": win})
    assert load == max(max(r) * 8 / (T * 3) for r in rows) >= 1.0


@pytest.mark.parametrize("fault", faults.NAMES)
def test_fault_is_not_correct(tiny_moonlight, cpu_peaks, fault):
    with faults.planted(fault):
        out = _run(tiny_moonlight)
    assert not out["line"]["correct"], out["stderr"]


def test_control_is_not_correct(tiny_moonlight):
    """The plain reference in the program's place, its products rounded
    to float8: it fails one of the numbers."""
    (_, prog), (_, ctrl) = calibrate.readings(tiny_moonlight, SEED,
                                              control=True)
    limits = tiny_moonlight.limits
    assert compare.passed([dict(c, limit=limits[c["name"]]) for c in prog])
    assert not compare.passed([dict(c, limit=limits[c["name"]])
                               for c in ctrl])
    assert all(math.isfinite(c["value"]) for c in ctrl)


def test_step_counts_latent_attention_by_hand():
    step = load_module("flops", "mla_moe_lm_train")
    a = {"D": 4, "H": 2, "r": 3, "nope": 2, "rope": 1, "v": 2, "V": 10,
         "n_dense": 1, "n_moe": 1, "F_dense": 6, "E": 3, "k": 2, "F": 5,
         "F_shared": 5}
    B, S = 1, 4
    T = B * S
    # W_q 4 -> 2*(2+1), W_kva 4 -> 3+1, W_kb 3 -> 2*2, W_vb 3 -> 2*2,
    # W_o 2*2 -> 4: 24 + 16 + 12 + 12 + 16 = 80 MACs a token
    proj = 2 * T * 80
    # scores over 2+1 and the sum over 2, per head, on half of the 4x4
    core = B * S * S * 2 * (2 + 1 + 2)
    dense = 3 * (proj + core + 6 * T * 4 * 6)
    moe = 3 * (proj + core + 2 * T * 4 * 3) + 2 * (6 * T * 2 * 4 * 5
                                                   + 6 * T * 4 * 5)
    head = 3 * 2 * T * 4 * 10
    assert step.step_flops(a, B, S) == dense + moe + head
    layers = load_module("flops", "mla_moe_lm_layers").layer_flops(a, B, S)
    assert sum(layers.values()) == step.step_flops(a, B, S)
    assert layers["attention"] == 3 * 2 * (proj + core)


def test_reference_freezes_the_bias_and_the_experts():
    assert mla_moe_lm.frozen("blocks/sub0/moe/e_score_correction_bias")
    assert mla_moe_lm.frozen("blocks/sub0/moe/wo")
    assert not mla_moe_lm.frozen("blocks/sub0/moe/router")
    assert not mla_moe_lm.frozen("blocks/sub0/attn/wkv_a")
    assert not mla_moe_lm.frozen("dense_blocks/sub0/mlp/wo")
