"""The cells end to end, untraced and traced, on the CPU at smoke size:
set-up, warm-up of every replica, the window through the router, the
reference check and the result line; and the int8 control, which the check
has to refuse where the program passes."""
import time

import pytest

from bench import cell as C
from bench.smoke_cells import SMOKE_LIMIT, run_apart, smoke_cell

KIND = "TPU v5 lite"  # the peak table's entry the readings would use


@pytest.mark.parametrize("name,trace", [("phi3-code", False),
                                        ("phi3-code", True),
                                        ("phi3-router-x4", False),
                                        ("phi3-router-x4", True)])
def test_cell_runs_end_to_end_at_smoke_size(name, trace):
    """A cell on several chips runs in a process of its own with as many
    host CPU devices: one replica on each, every one warmed up and used."""
    cell = smoke_cell(name)
    seed = 2**31 + 3
    if cell.chips == 1:
        r = C.run(cell, seed, 2.0, trace, time.time(), KIND)
    else:
        r = run_apart(name, seed, 3.0, trace)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert list(r)[-1] == "checks"
    assert r["checks"]["mean_logit_gap"]["value"] <= SMOKE_LIMIT
    rep = r["replicas"]
    assert r["device"]["count"] == cell.chips
    assert sorted(rep["devices"]) == list(range(cell.chips))
    assert min(rep["warm_requests"]) >= 2
    assert min(rep["window_requests"]) >= 1
    assert sum(rep["window_requests"]) == r["attempted"]
    if trace:
        # a CPU trace has no device plane: no device reading is made up;
        # the router's balance comes from its counters
        assert r["device"]["busy_s"] == 0.0
        assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}
        counted = {m["name"] for m in cell.per_layer} & {"replica_balance.p90"}
        assert set(r["metrics"]) == counted
        if counted:
            assert r["metrics"]["replica_balance.p90"]["value"] >= 1.0
    else:
        names = {m["name"] for m in cell.end_to_end}
        assert set(r["metrics"]) == names
        assert all(v["value"] > 0 for v in r["metrics"].values())


def test_int8_control_fails_the_check_the_program_passes():
    cell = smoke_cell("phi3-code", wide=True)
    seed = 11
    w = C.serve_window(cell, seed, 3.0, False, time.time())
    cell.config["correct"]["sample_tokens"] = 100
    g = C.compared_gaps(cell, seed, w.outcomes, control=True)
    program = C.check(cell.config, w.outcomes, g["served"])
    control = C.check(cell.config, w.outcomes, g["control"])
    assert program["compared_tokens"]["value"] >= 100
    assert C.passed(program) and not C.passed(control)
    assert g["served"].mean() <= SMOKE_LIMIT < g["control"].mean()
