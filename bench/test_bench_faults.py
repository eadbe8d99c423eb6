"""A run with the timed path broken underneath has to read ``correct``
false: the harness's look for a chip is skipped, the rest of a run is
driven at smoke size on the CPU."""
import time

from bench import cell as C
from bench.faults import altered_token, stale_cache
from bench.smoke_cells import smoke_cell

KIND = "TPU v5 lite"


def _run_with(monkeypatch, fault):
    monkeypatch.setattr(*fault())
    r = C.run(smoke_cell("phi3-code"), 5, 2.0, False, time.time(), KIND)
    assert not r["correct"]
    assert (r["checks"]["mean_logit_gap"]["value"]
            > r["checks"]["mean_logit_gap"]["limit"])


def test_a_token_altered_where_it_is_produced(monkeypatch):
    _run_with(monkeypatch, altered_token)


def test_a_decode_step_that_returns_its_cache_unchanged(monkeypatch):
    _run_with(monkeypatch, stale_cache)
