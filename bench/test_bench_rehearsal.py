"""The cell end to end, untraced and traced, on the CPU at smoke size:
set-up, warm-up, the window through the router, the reference check and
the result line; and the int8 control, which the check has to refuse
where the program passes."""
import time

import pytest

from bench import cell as C
from bench.smoke_cells import SMOKE_LIMIT, smoke_cell

KIND = "TPU v5 lite"  # the peak table's entry the readings would use


@pytest.mark.parametrize("name,trace", [("phi3-code", False),
                                        ("phi3-code", True)])
def test_cell_runs_end_to_end_at_smoke_size(name, trace):
    cell = smoke_cell(name)
    r = C.run(cell, 2**31 + 3, 2.0, trace, time.time(), KIND)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert list(r)[-1] == "checks"
    assert r["checks"]["mean_logit_gap"]["value"] <= SMOKE_LIMIT
    if trace:
        # a CPU trace has no device plane: no device reading is made up
        assert r["metrics"] == {} and r["device"]["busy_s"] == 0.0
        assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}
    else:
        names = {m["name"] for m in cell.end_to_end}
        assert set(r["metrics"]) == names
        assert all(v["value"] > 0 for v in r["metrics"].values())


def test_int8_control_fails_the_check_the_program_passes():
    cell = smoke_cell("phi3-code", wide=True)
    seed = 11
    w = C.serve_window(cell, seed, 3.0, False, time.time())
    cell.config["correct"]["sample_tokens"] = 100
    g = C.compared_gaps(cell.config, seed, w.outcomes, control=True)
    program = C.check(cell.config, w.outcomes, g["served"])
    control = C.check(cell.config, w.outcomes, g["control"])
    assert program["compared_tokens"]["value"] >= 100
    assert C.passed(program) and not C.passed(control)
    assert g["served"].mean() <= SMOKE_LIMIT < g["control"].mean()
