"""CPU rehearsal of ``chip_smoke.py``: its phase functions on reduced
configs, Pallas kernels in interpret mode.

The script itself needs a TPU; this drives the same control flow — the
federated pipeline through every phase, the paged-vs-contiguous serving
check and the expert-parallel training comparison — at toy sizes, so
a broken path shows up on every change rather than on the chip.
"""
import os
import sys

import jax
import pytest

from repro.configs import get_config

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import chip_smoke  # noqa: E402


@pytest.fixture
def rec():
    r = chip_smoke.Recorder(ir_dir=None)
    yield r
    r.close()


def _reduced(name, **kw):
    return get_config(name, variant="reduced").replace(use_pallas=True, **kw)


def test_smoke_configs_keep_published_widths():
    moe, (gpt2, gpt2m), moe_cuts, device_cuts = chip_smoke.smoke_configs()
    full = get_config("qwen2-moe-a2.7b")
    for f in ("d_model", "n_heads", "head_dim", "n_experts", "top_k",
              "moe_d_ff", "vocab_size", "n_shared_experts"):
        assert getattr(moe, f) == getattr(full, f), f
    assert moe.use_pallas and moe.n_layers == chip_smoke.MOE_LAYERS
    assert gpt2.d_model == 768 and gpt2m.d_model == 1024
    assert gpt2.vocab_size == gpt2m.vocab_size == full.vocab_size
    assert len(moe_cuts) == len(device_cuts) == 2


def test_train_and_serve_phases_rehearsal(rec):
    moe = _reduced("qwen2-moe-a2.7b")
    devices = (_reduced("gpt2"), _reduced("gpt2-medium"))
    params, report = chip_smoke.train_phase(moe, devices, rec, seq_len=32,
                                            steps=2, batch=2)
    assert len(report["uploads"]) == 4
    for part in ("fleet local training", "phase II distill",
                 "phase III tune", "eval"):
        assert rec.parts[part]["n"] >= 1, part
    got = chip_smoke.serve_phase(params, moe, rec, gen=4, chunk_len=8,
                                 block_len=8, prefix_len=16,
                                 prompt_lens=(16, 24, 40))
    assert sorted(got) == [0, 1, 2]
    assert all(len(t) == 4 for t in got.values())
    assert {"serve paged", "serve contiguous"} <= set(rec.parts)


def test_train_ep_phase_rehearsal(rec):
    cfg = _reduced("qwen2-moe-a2.7b", capacity_factor=2.0)
    n = len(jax.devices())
    ep, one = chip_smoke.train_ep_phase(cfg, rec, n_chips=n, steps=2,
                                        batch=2, seq=16)
    assert len(ep) == len(one) == 2


def test_main_refuses_without_tpu(capsys):
    assert chip_smoke.main([]) == 2
    out = capsys.readouterr()
    assert out.out == ""
    assert "needs a TPU" in out.err
