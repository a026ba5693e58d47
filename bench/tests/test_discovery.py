"""Every cell of BENCHMARK.json is found by name, with all it names."""
import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT
from harness import spec


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", [w["name"] for w in _bench()["workloads"]])
def test_cell_has_its_files(name):
    cell = spec.load_cell(name)
    assert cell.name == name
    spec.load_module("jobs", cell.traffic["job"])
    spec.load_module("reference", cell.config["model_type"])
    spec.load_module("systems", cell.config["model_type"])
    assert set(cell.limits) == {"loss_gap", "grad_gap", "change_gap"}
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        spec.load_module("metrics", m["name"])
        assert m["moves"] in names


def test_config_cuts_match_the_benchmark():
    for c in _bench()["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert cfg["source"] == c["source"]


def test_unknown_cell_is_refused():
    with pytest.raises(KeyError):
        spec.load_cell("no.such.cell")


def test_no_chip_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(BENCH, "run.py"),
                        "--workload", _bench()["workloads"][0]["name"],
                        "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, cwd=ROOT,
                       timeout=300)
    assert p.returncode == 2
    assert p.stdout == ""
    assert "needs a TPU" in p.stderr


def test_unknown_device_kind_is_an_error():
    from harness import device
    assert device.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        device.peaks("cpu")
