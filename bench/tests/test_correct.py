"""A run at CPU size, past the harness's look for a chip: ``correct``
holds for the program, and comes out false for the control and for
each fault a training cell can have, under the cell's own limits."""
import math
import time

import jax
import pytest

import calibrate
import faults
from harness import cell as cell_mod
from harness import compare

SEED = 2 ** 31 + 12345


def _run(cell):
    out = cell_mod.run(cell, SEED, 0.5, False, t_start=time.time(),
                       devices=jax.devices())
    assert set(out["line"]) >= {"correct", "attempted", "failed", "metrics",
                                "device", "checks"}
    assert list(out["line"])[-1] == "checks"
    return out


def test_program_is_correct(tiny_cell, cpu_peaks):
    out = _run(tiny_cell)
    line = out["line"]
    assert line["correct"], out["stderr"]
    assert line["failed"] == 0 and line["attempted"] >= 2
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert out["stderr"][-1].startswith("check change_gap")


def test_end_to_end_metric_the_job_does_not_measure(tiny_cell, cpu_peaks):
    """A metric for every cell is left out where the job does not measure
    it; a cell listed for one that its job does not measure is a fault."""
    other = {"name": "ttft_p95_ms", "unit": "ms", "better": "lower",
             "bound": 0.05, "source": "host_clock"}
    tiny_cell.end_to_end = tiny_cell.end_to_end + [other]
    assert "ttft_p95_ms" not in _run(tiny_cell)["line"]["metrics"]
    tiny_cell.end_to_end[-1] = dict(other, workloads=[tiny_cell.name])
    with pytest.raises(RuntimeError, match="ttft_p95_ms"):
        _run(tiny_cell)


@pytest.mark.parametrize("fault", faults.NAMES)
def test_fault_is_not_correct(tiny_cell, cpu_peaks, fault):
    with faults.planted(fault):
        out = _run(tiny_cell)
    assert not out["line"]["correct"], out["stderr"]


def test_control_is_not_correct(tiny_cell):
    """The plain reference in the program's place, its products rounded
    to float8: it fails one of the numbers."""
    (_, prog), (_, ctrl) = calibrate.readings(tiny_cell, SEED, control=True)
    assert compare.passed([dict(c, limit=tiny_cell.limits[c["name"]])
                           for c in prog])
    assert not compare.passed([dict(c, limit=tiny_cell.limits[c["name"]])
                               for c in ctrl])
    assert all(math.isfinite(c["value"]) for c in ctrl)
