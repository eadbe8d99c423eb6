"""A run with the timed path broken underneath has to read ``correct``
false: the harness's look for a chip is skipped, the rest of a run is
driven at smoke size on the CPU (a cell on several chips in a process of
its own, with as many host CPU devices, the fault planted in every
replica)."""
import time

import pytest

from bench import cell as C
from bench.faults import FAULTS
from bench.smoke_cells import run_apart, smoke_cell

KIND = "TPU v5 lite"


# altered_token: a token altered where it is produced; stale_cache: a decode
# step that returns its cache unchanged
@pytest.mark.parametrize("name", ["phi3-code", "phi3-router-x4"])
@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_broken_timed_path_reads_not_correct(monkeypatch, name, fault):
    cell = smoke_cell(name)
    if cell.chips == 1:
        monkeypatch.setattr(*FAULTS[fault]())
        r = C.run(cell, 5, 2.0, False, time.time(), KIND)
    else:
        r = run_apart(name, 5, 3.0, fault=fault)
    assert not r["correct"]
    assert (r["checks"]["mean_logit_gap"]["value"]
            > r["checks"]["mean_logit_gap"]["limit"])
