"""Put the benchmark's own modules and the system under test on the path,
and give the tests a tiny cell that runs on the CPU."""
import copy
import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (os.path.join(ROOT, "src"), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_QWEN = {
    "hidden_size": 64, "intermediate_size": 64, "moe_intermediate_size": 32,
    "shared_expert_intermediate_size": 64, "num_experts": 4,
    "num_experts_per_tok": 2, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 4, "vocab_size": 256,
}
TINY_TRAFFIC = {"seq_len": 32, "batch": 4, "pool": 4}


@pytest.fixture
def tiny_cell():
    """The cell ``qwen2moe.tune.xla`` at CPU size: its own job, traffic
    mix, limits and configuration family, with small widths."""
    from harness import spec
    cell = spec.load_cell("qwen2moe.tune.xla")
    cell = copy.deepcopy(cell)
    cell.config.update(TINY_QWEN)
    cell.traffic.update(TINY_TRAFFIC)
    return cell


@pytest.fixture
def cpu_peaks(monkeypatch):
    from harness import device
    monkeypatch.setattr(device, "peaks", lambda kind: {
        "bf16_flops": 1e12, "hbm_bytes_per_s": 1e11})
