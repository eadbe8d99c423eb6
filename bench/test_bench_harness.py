"""CPU checks of the benchmark's own parts: manifest, traffic, work counts,
percentiles, the trace reducer, and the refusal of a CPU device."""
import json
import os
import subprocess
import sys

import pytest

from bench import manifest, traffic, work
from bench.cell import Context
from bench.devtrace import DeviceTrace, Event

ROOT = manifest.ROOT
BENCH = manifest.load()


# -- manifest ---------------------------------------------------------------


def _all_names():
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in BENCH[key]:
            yield entry["name"]
    for w in BENCH["workloads"]:
        yield w["config"]
        yield w["traffic"]
    for c in BENCH["configs"]:
        yield from c["reduced"]


def test_names_and_units_use_allowed_characters():
    for name in _all_names():
        assert manifest.NAME.match(name), name
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert manifest.UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in BENCH[k]]
    assert len(names) == len(set(names))


def test_per_layer_moves_and_workloads_agree_with_cells():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e, m
        for cell in m.get("workloads", cells):
            assert cell in cells
            # the cell reports the end-to-end metric this one moves
            assert manifest.reports(e2e[m["moves"]], cell), (m, cell)


def test_every_cell_has_setup_another_e2e_and_a_per_layer_metric():
    for w in BENCH["workloads"]:
        cell = manifest.cell(w["name"])
        names = [m["name"] for m in cell.end_to_end]
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer


def test_every_metric_has_a_reader_and_every_file_is_under_paths():
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert callable(manifest.reader(m["name"]))
    for c in BENCH["configs"]:
        assert c["file"].startswith(BENCH["paths"][0] + "/")
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        for key in c["reduced"]:
            assert key in cfg


TOY_FAMILY = """
import jax.numpy as jnp


def weight_leaves(cfg):
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    return [("embedding", (v, d), "embed", 0, jnp.float32),
            ("lm_head", (d, v), "normal", d, jnp.float32)]


def forward(w, toks, rows, cfg, dense):
    return dense(w["embedding"][toks][rows], w["lm_head"])


def request_work(cfg, prompt, out):
    return {"flops": 2.0 * cfg["hidden_size"] * cfg["vocab_size"] * out,
            "toy_scan_flops": 197e12 * prompt, "toy_scan_bytes": 1.0}
"""


def _new_files(tmp_path, model_type="toyfam"):
    """A cell, its configuration, its family, its mix and two metrics, as
    new files and entries of a copy of ``BENCHMARK.json`` under
    ``tmp_path``."""
    bench = json.loads(json.dumps(BENCH))
    for d in ("configs", "models", "traffic", "metrics"):
        (tmp_path / "bench" / d).mkdir(parents=True)
    (tmp_path / "bench" / "configs" / "tiny-model.json").write_text(
        json.dumps({"name": "tiny-model", "model_type": model_type,
                    "hidden_size": 8, "vocab_size": 16,
                    "torch_dtype": "float32"}))
    (tmp_path / "bench" / "models" / "toyfam.py").write_text(TOY_FAMILY)
    (tmp_path / "bench" / "traffic" / "burst.json").write_text(
        json.dumps({"loop": "open", "rate_rps": 3.0,
                    "prompt_tokens": [4, 8], "output_tokens": [2, 4]}))
    (tmp_path / "bench" / "metrics" / "queue_wait_ms.lat.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    (tmp_path / "bench" / "metrics" / "toy_scan_roofline.py").write_text(
        "def read(ctx):\n    return ctx.roofline('_toy_scan', 'toy_scan')\n")
    bench["configs"].append({"name": "tiny-model", "source": "x",
                             "file": "bench/configs/tiny-model.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "tiny-burst", "config": "tiny-model",
                               "traffic": "burst", "chips": 1, "why": "x"})
    for name, unit in (("queue_wait_ms.lat", "ms"),
                       ("toy_scan_roofline", "%")):
        bench["per_layer"].append({"name": name, "unit": unit,
                                   "better": "lower", "source": "host_clock",
                                   "layer": "router",
                                   "moves": "latency_p90_ms",
                                   "workloads": ["tiny-burst"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(tmp_path)


def test_a_cell_of_new_files_is_found_by_name(tmp_path):
    """A later change adds a cell, its configuration, its model family, its
    mix and metrics as new files plus entries: the harness finds all of
    them by name, and the family's reference, weights and useful work are
    the ones used, with no edit to a file that is there."""
    import numpy as np

    from bench import reference

    root = _new_files(tmp_path)
    cell = manifest.cell("tiny-burst", root=root)
    assert cell.config["hidden_size"] == 8
    assert cell.traffic["rate_rps"] == 3.0
    assert [m["name"] for m in cell.per_layer] == ["queue_wait_ms.lat",
                                                   "toy_scan_roofline"]
    assert [m["name"] for m in cell.end_to_end] == ["setup_s"]
    # the family's useful work, summed key by key
    assert work.totals(cell.family, cell.config, [(3, 2), (5, 1)]) == {
        "flops": 2.0 * 8 * 16 * 3, "toy_scan_flops": 197e12 * 8,
        "toy_scan_bytes": 2.0}
    # the family's weights and forward make the reference's gaps
    cases = [([1, 2, 3], [4, 5]), ([7], [9, 9, 2])]
    gaps = reference.gaps(cell.family, cell.config, 3, cases)["served"]
    w = reference.make_weights(cell.family, cell.config, 3)
    assert w["embedding"].shape == (16, 8) and w["lm_head"].shape == (8, 16)
    logits = np.asarray(w["embedding"]) @ np.asarray(w["lm_head"])
    want = [logits[t].max() - logits[t][s] for p, served in cases
            for t, s in zip(p[-1:] + served[:-1], served)]
    np.testing.assert_allclose(gaps, want, rtol=1e-5, atol=1e-6)
    # a metric of a new kernel reads the family's new pair of work keys
    ctx = Context(loop="open", outcomes=[traffic.Outcome(
                      traffic.Request(0, [1] * 4, 2), 0.0, 1.0,
                      tokens=[1, 2])],
                  t0=0.0, t_end=1.0, setup_s=1.0, cfg=cell.config,
                  family=cell.family, peak=work.PEAKS["TPU v5 lite"],
                  trace=DeviceTrace([_ev("XLA Ops", "_toy_scan.3", 0, 8e9)]))
    got = manifest.read_metrics(cell.per_layer, ctx, root=root)
    assert got == {"queue_wait_ms.lat": {"value": 42.0, "unit": "ms"},
                   "toy_scan_roofline": {"value": pytest.approx(50.0),
                                         "unit": "%"}}
    with pytest.raises(KeyError):
        manifest.cell("no-such-cell", root=root)


def test_a_family_without_a_module_fails_by_name(tmp_path):
    root = _new_files(tmp_path, model_type="no_such_family")
    with pytest.raises(FileNotFoundError,
                       match=r"bench/models/no_such_family\.py"):
        manifest.cell("tiny-burst", root=root)


# -- traffic ----------------------------------------------------------------

CODE = manifest.cell("phi3-code").traffic
# an open-loop mix as a later cell would add it: the generator's other loop
OPEN = {"loop": "open", "rate_rps": 1.2, "prompt_tokens": [64, 512],
        "output_tokens": [16, 128]}


def _sig(reqs):
    return [(len(r.prompt), r.max_new, round(r.due, 9), r.prompt[:4])
            for r in reqs]


def test_a_split_metric_is_read_by_its_quantitys_reader(tmp_path):
    """``idle_share.lat`` has no file of its own: ``idle_share.py`` reads
    it; a file of its own takes precedence."""
    assert manifest.reader("idle_share.lat") is not None
    ctx = _ctx([], 0.0, 2.0)
    ctx.trace = DeviceTrace([_ev("XLA Ops", "fusion.1", 0, 0.5e9)])
    assert manifest.reader("idle_share.lat")(ctx) == pytest.approx(75.0)
    (tmp_path / "bench" / "metrics").mkdir(parents=True)
    for name, v in (("idle_share", 1.0), ("idle_share.lat", 2.0)):
        (tmp_path / "bench" / "metrics" / (name + ".py")).write_text(
            f"def read(ctx):\n    return {v}\n")
    assert manifest.reader("idle_share.lat", str(tmp_path))(None) == 2.0
    assert manifest.reader("idle_share.tput", str(tmp_path))(None) == 1.0
    with pytest.raises(FileNotFoundError):
        manifest.reader("no_such_metric.lat", str(tmp_path))


def test_same_seed_same_traffic_other_seed_other_order():
    a = traffic.open_requests(OPEN, 50, 2**31 + 5, 32064)
    b = traffic.open_requests(OPEN, 50, 2**31 + 5, 32064)
    c = traffic.open_requests(OPEN, 50, 12345, 32064)
    assert _sig(a) == _sig(b)
    assert _sig(a) != _sig(c)
    # the same sizes and gaps, in another order
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in c)
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in c)
    gaps = lambda rs: sorted(round(y.due - x.due, 9)
                             for x, y in zip(rs, rs[1:]))
    assert gaps(a) == gaps(c)


def test_closed_pool_blocks_carry_the_same_work_for_every_seed():
    def first(seed, n=64):
        it = traffic.closed_pool(CODE, seed, 32064)
        return [next(it) for _ in range(n)]

    a, b = first(1), first(2**31 + 99)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    k = traffic.STRATA
    for i in range(0, 64, k):  # every block of STRATA requests
        assert (sorted(len(r.prompt) for r in a[i:i + k])
                == sorted(len(r.prompt) for r in b[i:i + k]))
    lo, hi = CODE["prompt_tokens"]
    assert all(lo <= len(r.prompt) <= hi for r in a)
    assert all(0 < t < 32064 for r in a for t in r.prompt)


def test_open_mix_sizes_rate_and_range():
    reqs = traffic.open_requests(OPEN, 40, 3, 32064)
    assert len(reqs) == round(OPEN["rate_rps"] * 40)
    assert reqs[0].due == 0 and all(
        x.due <= y.due for x, y in zip(reqs, reqs[1:]))
    lo, hi = OPEN["output_tokens"]
    assert all(lo <= r.max_new <= hi for r in reqs)
    # stratified exponential gaps: their mean is close to 1 / rate
    mean_gap = reqs[-1].due / (len(reqs) - 1)
    assert abs(mean_gap * OPEN["rate_rps"] - 1) < 0.15


def _ctx(outcomes, t0, t_end, loop="open"):
    cell = manifest.cell("phi3-code")
    return Context(loop=loop, outcomes=outcomes, t0=t0, t_end=t_end,
                   setup_s=1.0, cfg=cell.config, family=cell.family,
                   peak=work.PEAKS["TPU v5 lite"])


def test_latency_is_timed_from_the_due_time():
    """A request sent late (the generator stalled) still counts the wait."""
    reqs = [traffic.Request(i, [1, 2, 3], 2, due=0.1 * i) for i in range(10)]
    t0 = 100.0
    # every request was sent 2 s after it was due and answered 0.5 s later
    outs = [traffic.Outcome(r, sent=t0 + r.due + 2.0,
                            done=t0 + r.due + 2.5, tokens=[5, 6])
            for r in reqs]
    p90 = manifest.reader("latency_p90_ms")(_ctx(outs, t0, t0 + 3.4))
    assert p90 == pytest.approx(2500.0)
    # a failed request gives no latency reading
    outs[3] = traffic.Outcome(reqs[3], t0, t0 + 1, error="boom")
    assert manifest.reader("latency_p90_ms")(_ctx(outs, t0, t0 + 3.4)) is None


def test_open_loop_sends_on_schedule_whatever_the_answers():
    import threading
    import time

    gate = threading.Event()

    def send(req):  # the first answer is held until the last is sent
        if req.index == 0:
            gate.wait(5)
        if req.index == 4:
            gate.set()
        return [1] * req.max_new

    reqs = [traffic.Request(i, [1], 1, due=0.05 * i) for i in range(5)]
    t0 = time.perf_counter()
    outs = traffic.run_open(send, reqs, t0)
    assert all(o.ok for o in outs)
    assert all(o.sent - t0 - o.request.due < 0.04 for o in outs)
    assert outs[0].done >= outs[4].sent


def test_sweep_judges_each_step_and_takes_the_highest_that_keeps_up():
    """Per step of the staircase: offered rate, completions in the step's
    interval shifted by the lowest step's median latency, p90; the knee is
    the highest rate whose step keeps up, a low step that misses by one
    answer notwithstanding."""
    from bench import sweep

    step_s, t0 = 10.0, 0.0
    mix = {"loop": "open", "rate_rps": 1.0, "prompt_tokens": [8, 16],
           "output_tokens": [2, 4]}
    rates = [1.0, 2.0, 3.0]
    steps = sweep.staircase(mix, rates, step_s, 5, 100)
    assert [len(s) for s in steps] == [10, 20, 30]
    assert all(k * step_s <= r.due < (k + 1) * step_s
               for k, s in enumerate(steps) for r in s)
    assert len({r.index for s in steps for r in s}) == 60
    # steps 1 and 2 answered 1 s after due; step 3's queue grows by 2 s a
    # request, so it neither completes in its interval nor keeps its p90
    outs = [traffic.Outcome(r, sent=r.due, done=r.due + 1.0, tokens=[1])
            for s in steps[:2] for r in s]
    outs += [traffic.Outcome(r, sent=r.due, done=r.due + 1.0 + 2.0 * j,
                             tokens=[1]) for j, r in enumerate(steps[2])]
    rows = sweep.judge(rates, steps, outs, t0, step_s, [30, 30])
    assert [r["keeps_up"] for r in rows] == [True, True, False]
    assert rows[0]["p50_ms"] == pytest.approx(1000.0)
    assert rows[1]["completed_rps"] == pytest.approx(2.0)
    assert rows[-1]["replica_requests"] == [30, 30]
    assert sweep.knee(rows) == 2.0
    rows[0]["keeps_up"] = False
    assert sweep.knee(rows) == 2.0
    assert sweep.knee([dict(r, keeps_up=False) for r in rows]) is None


def test_tokens_per_s_counts_all_work_over_all_time():
    reqs = [traffic.Request(i, [1] * 100, 4) for i in range(3)]
    outs = [traffic.Outcome(r, sent=10.0, done=10.0 + i, tokens=[1] * 4)
            for i, r in enumerate(reqs, 1)]
    got = manifest.reader("tokens_per_s")(_ctx(outs, 10.0, 13.0, "closed"))
    assert got == pytest.approx(3 * 104 / 3.0)


def test_percentile():
    xs = [5.0, 1.0, 4.0, 2.0, 3.0]
    assert work.percentile(xs, 0) == 1.0
    assert work.percentile(xs, 50) == 3.0
    assert work.percentile(xs, 90) == pytest.approx(4.6)
    assert work.percentile(xs, 100) == 5.0
    with pytest.raises(ValueError):
        work.percentile([], 90)


# -- useful work, worked by hand ----------------------------------------------

FAMILY = manifest.cell("phi3-code").family  # bench/models/phi3.py
PHI3 = FAMILY.Shape.of(manifest.cell("phi3-code").config)
# grouped-query attention: granite-3-8b's published widths at 20 layers
GRANITE = FAMILY.Shape.of({"num_hidden_layers": 20, "hidden_size": 4096,
                         "num_attention_heads": 32, "num_key_value_heads": 8,
                         "intermediate_size": 12800, "vocab_size": 49155})


def test_shapes_of_both_configurations():
    assert (PHI3.layers, PHI3.d, PHI3.heads, PHI3.kv_heads, PHI3.head_dim,
            PHI3.d_ff, PHI3.vocab) == (32, 3072, 32, 32, 96, 8192, 32064)
    assert (GRANITE.layers, GRANITE.d, GRANITE.heads, GRANITE.kv_heads,
            GRANITE.head_dim, GRANITE.d_ff, GRANITE.vocab) == (
        20, 4096, 32, 8, 128, 12800, 49155)
    # phi3: 32 x (4 x 3072^2 + 3 x 3072 x 8192) + 2 x 3072 x 32064;
    # granite: 20 x (2 x 4096^2 + 2 x 4096 x 1024 + 3 x 4096 x 12800)
    # + 2 x 4096 x 49155
    phi3 = PHI3.layers * PHI3.layer_params + 2 * PHI3.head_params
    granite = GRANITE.layers * GRANITE.layer_params + 2 * GRANITE.head_params
    assert phi3 == 32 * 113_246_208 + 197_001_216 == 3_820_879_872
    assert granite == 20 * 199_229_440 + 402_677_760 == 4_387_266_560


def test_decode_kv_bytes_at_valid_contexts():
    # phi3, prompt 100, 3 tokens: useful decode steps at positions 100 and
    # 101 attend over 101 and 102 keys; K and V, 32 heads x 96, bf16, 32
    # layers
    flops, nbytes = FAMILY.decode_attn_work(PHI3, 100, 3)
    assert nbytes == 32 * 2 * 32 * 96 * (101 + 102) * 2
    assert flops == 32 * 4 * 32 * 96 * (101 + 102)
    # granite: GQA reads 8 of 32 heads' K/V, 20 layers; one token: no
    # decode step is useful (the first token comes with the prompt)
    assert FAMILY.decode_attn_work(GRANITE, 2000, 1) == (0.0, 0.0)
    flops, nbytes = FAMILY.decode_attn_work(GRANITE, 2000, 2)
    assert nbytes == 20 * 2 * 8 * 128 * 2001 * 2
    assert flops == 20 * 4 * 32 * 128 * 2001


def test_causal_prefill_flops_and_bytes():
    # granite, prompt 1024: sum of 1..1024 = 524,800 query-key pairs per
    # head, 4 FLOPs per pair per head dim
    flops, nbytes = FAMILY.flash_work(GRANITE, 1024)
    assert flops == 20 * 4 * 32 * 128 * 524_800
    assert nbytes == 20 * (2 * 32 + 2 * 8) * 128 * 1024 * 2
    assert work.causal_pairs(0, 1023) == 524_800
    assert work.causal_pairs(5, 4) == 0


def test_step_flops_of_one_request():
    # phi3, prompt 2, 2 tokens: 3 positions through the layers, the head at
    # 2, attention pairs 1 + 2 + 3 = 6
    per_layer = (3072 * 32 * 96 * 2 + 2 * 3072 * 32 * 96
                 + 3 * 3072 * 8192)
    want = (2 * 32 * per_layer * 3 + 2 * 3072 * 32064 * 2
            + 32 * 4 * 32 * 96 * 6)
    assert FAMILY.request_flops(PHI3, 2, 2) == want


# -- the phi3 family, pinned to the values the harness gave before families
# were split out of bench/work.py and bench/reference.py

# (P, O): flops, flash FLOPs, flash bytes, decode FLOPs, decode bytes of
# phi3-mini-3.8b
PINNED_WORK = {
    (1, 1): (7445151744, 393216, 786432, 0, 0),
    (2, 2): (22139633664, 1179648, 1572864, 1179648, 1179648),
    (100, 3): (741927813120, 1985740800, 78643200, 79822848, 79822848),
    (1125, 4): (8426640900096, 249053184000, 884736000, 1329463296,
                1329463296),
    (2000, 42): (15620354211840, 786825216000, 1572864000, 32582270976,
                 32582270976),
    (676, 33): (5236604928000, 89978044416, 531628032, 8713666560,
                8713666560),
    (1538, 504): (15711368773632, 465367597056, 1209532416, 354039889920,
                  354039889920),
}
WORK_KEYS = ("flops", "flash_flops", "flash_bytes", "decode_flops",
             "decode_bytes")


@pytest.mark.parametrize("prompt,out", sorted(PINNED_WORK))
def test_phi3_request_work_is_pinned(prompt, out):
    cfg = manifest.cell("phi3-code").config
    got = FAMILY.request_work(cfg, prompt, out)
    assert got == dict(zip(WORK_KEYS, map(float, PINNED_WORK[prompt, out])))


def test_phi3_work_totals_and_weight_leaves_are_pinned():
    import jax.numpy as jnp

    cfg = manifest.cell("phi3-code").config
    assert work.totals(FAMILY, cfg, sorted(PINNED_WORK)) == {
        "flops": 45766481412096.0, "flash_flops": 1593211355136.0,
        "flash_bytes": 4279762944.0, "decode_flops": 396746293248.0,
        "decode_bytes": 396746293248.0}
    L, d, H, D, F, V = 32, 3072, 32, 96, 8192, 32064
    assert [(n, s, i, f, jnp.dtype(dt).name) for n, s, i, f, dt
            in FAMILY.weight_leaves(cfg)] == [
        ("wk", (L, d, H, D), "normal", d, "bfloat16"),
        ("wo", (L, H, D, d), "normal", H * D, "bfloat16"),
        ("wq", (L, d, H, D), "normal", d, "bfloat16"),
        ("wv", (L, d, H, D), "normal", d, "bfloat16"),
        ("ln_attn", (L, d), "ones", 0, "float32"),
        ("ln_mlp", (L, d), "ones", 0, "float32"),
        ("w1", (L, d, F), "normal", d, "bfloat16"),
        ("w2", (L, F, d), "normal", F, "bfloat16"),
        ("w3", (L, d, F), "normal", d, "bfloat16"),
        ("embedding", (V, d), "embed", 0, "bfloat16"),
        ("lm_head", (d, V), "normal", d, "bfloat16"),
        ("ln_f", (d,), "ones", 0, "float32")]


def test_phi3_reference_weights_and_gaps_are_pinned():
    """At smoke size on the CPU: each leaf's sum of the seeded weights, and
    the reference's and the int8 control's gaps on two fixed cases."""
    import numpy as np

    from bench import reference
    from bench.smoke_cells import smoke_cell

    cfg, seed = smoke_cell("phi3-code").config, 2**31 + 3
    w = reference.make_weights(FAMILY, cfg, seed)
    sums = {k: float(np.asarray(v, np.float64).sum()) for k, v in w.items()}
    assert sums == pytest.approx({
        "embedding": -39.37105609464925, "lm_head": -1.0163471805281006,
        "ln_attn": 128.0, "ln_f": 64.0, "ln_mlp": 128.0,
        "w1": 2.5414179604695164, "w2": -14.2887108702962,
        "w3": 28.733529686224983, "wk": -0.3373847334078164,
        "wo": -0.24251247818028787, "wq": -20.027137755985677,
        "wv": 5.690725172531529}, rel=1e-5)
    cases = [([217, 163, 131, 69, 79, 11, 20, 5, 45, 208, 166, 233, 129, 155,
               248, 187, 162, 139, 143, 239], [71, 209, 172, 1, 101]),
             ([219, 142, 9, 196, 187, 216, 45], [23, 221, 6])]
    g = reference.gaps(FAMILY, cfg, seed, cases, control=True)
    np.testing.assert_allclose(g["served"], [
        2.21622896194458, 2.5446524620056152, 1.9911556243896484,
        1.3523038625717163, 3.3788070678710938, 1.8100950717926025,
        0.648322343826294, 2.1982221603393555], rtol=1e-5)
    np.testing.assert_array_equal(g["control"], np.zeros(8))


def test_roofline_bound_and_peaks():
    peak = work.peaks("TPU v5 lite")
    assert work.least_time(197e12, 1.0, peak) == 1.0      # compute-bound
    assert work.least_time(1.0, 2 * 819e9, peak) == 2.0   # memory-bound
    with pytest.raises(KeyError):
        work.peaks("cpu")


# -- the trace reducer ---------------------------------------------------------

DEV = "/device:TPU:0"


def _ev(line, name, start, dur, plane=DEV):
    return Event(plane, line, name, start, dur)


def test_trace_reducer_on_a_hand_made_trace():
    ms = 1e6
    events = [
        _ev("XLA Modules", "jit_prefill(11)", 0, 4 * ms),
        _ev("XLA Ops", "fusion.1", 0, 1 * ms),
        _ev("XLA Ops", "_flash_attention.2", 1 * ms, 2 * ms),
        _ev("XLA Ops", "fusion.3", 2.5 * ms, 1.5 * ms),  # overlaps the last
        _ev("XLA Modules", "jit_decode(12)", 10 * ms, 2 * ms),
        _ev("XLA Ops", "fusion.1", 10 * ms, 2 * ms),
        _ev("XLA Modules", "jit_decode(12)", 13 * ms, 2 * ms),
        _ev("XLA Ops", "while.4", 13 * ms, 2 * ms),      # a loop ...
        _ev("XLA Ops", "_decode_attention.5", 13.5 * ms, 1 * ms),  # ... body
        _ev("python3", "serve_job pump", 3 * ms, 8 * ms, plane="/host:CPU"),
        _ev("python3", "np.asarray", 12 * ms, 0.5 * ms, plane="/host:CPU"),
    ]
    t = DeviceTrace(events)
    assert t.devices == [DEV]
    assert t.busy_s() == pytest.approx(8e-3)   # [0,4] + [10,12] + [13,15]
    assert t.module_time("decode") == (pytest.approx(4e-3), 2)
    assert t.module_time("prefill") == (pytest.approx(4e-3), 1)
    assert t.module_time("insert") == (0, 0)
    assert t.kernel_time("_flash_attention") == (pytest.approx(2e-3), 1)
    assert t.kernel_time("_decode_attention") == (pytest.approx(1e-3), 1)
    top = dict(t.top_ops(10))
    assert top["fusion"] == pytest.approx(4.5e-3)
    assert top["while"] == pytest.approx(1e-3)  # its own time, body excluded
    gaps = t.idle_gaps(10)
    assert gaps[0] == ["serve_job pump -> jit_decode", pytest.approx(6e-3)]
    assert gaps[1] == ["np.asarray -> jit_decode", pytest.approx(1e-3)]


def test_idle_gaps_and_busy_time_cover_every_device():
    """Four replicas, one a device: the longest gaps are looked for on every
    device plane, each named by the program that ran next on its own
    device; busy time stays the mean over the devices."""
    ms = 1e6
    events = []
    for n in range(4):  # device n idles (n + 1) ms between two programs
        plane = f"/device:TPU:{n}"
        events += [_ev("XLA Modules", f"jit_decode({n})", 0, 2 * ms, plane),
                   _ev("XLA Ops", "fusion.1", 0, 2 * ms, plane),
                   _ev("XLA Modules", f"jit_prefill({n})", (3 + n) * ms,
                       1 * ms, plane),
                   _ev("XLA Ops", "fusion.2", (3 + n) * ms, 1 * ms, plane)]
    t = DeviceTrace(events)
    assert len(t.devices) == 4
    assert t.busy_s() == pytest.approx(3e-3)
    gaps = t.idle_gaps(3)
    assert [g[1] for g in gaps] == pytest.approx([4e-3, 3e-3, 2e-3])
    assert all(g[0] == "host idle -> jit_prefill" for g in gaps)


def test_replica_balance_is_the_busiest_replica_over_the_mean():
    read = manifest.reader("replica_balance.p90")
    ctx = _ctx([], 0.0, 1.0)
    for took, want in (([10, 10, 10, 10], 1.0), ([12, 10, 9, 9], 1.2),
                       ([40, 0, 0, 0], 4.0), ([7], None), (None, None),
                       ([0, 0], None)):
        ctx.replica_requests = took
        assert read(ctx) == (pytest.approx(want) if want else None)


def test_trace_reducer_on_a_recorded_excerpt():
    """Two decode ticks of phi3-mini (8 slots x 1,024) recorded on a v5e: every decode
    execution runs the decode kernel once per layer (32), inside it."""
    with open(os.path.join(ROOT, "bench", "trace_excerpt.json")) as f:
        t = DeviceTrace([Event(*e) for e in json.load(f)["events"]])
    s, n = t.module_time("decode")
    assert n == 2 and 0.07 < s / n < 0.09
    ks, kn = t.kernel_time("_decode_attention")
    assert kn == 32 * n and 0 < ks < s
    assert t.kernel_time("_flash_attention") == (0, 0)
    busy = t.busy_s()
    assert s <= busy + 1e-6
    # own times of all operations add up to the busy time (nothing runs
    # twice, nothing is lost to nesting)
    assert sum(t.self_times().values()) / 1e9 == pytest.approx(busy, rel=1e-3)
    assert "_decode_attention" in [name for name, _ in t.top_ops(10)]
    assert t.self_times()["while"] < 0.05 * busy * 1e9  # the layer loop

    gaps = t.idle_gaps(3)
    assert len(gaps) == 3 and all(g[1] > 0 for g in gaps)


def test_trace_reducer_reads_a_recorded_profile(tmp_path):
    """The loader reads what jax.profiler writes (here a CPU trace: no
    device plane, so nothing is busy and every device reading is absent)."""
    import jax
    import jax.numpy as jnp

    from bench import devtrace

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    t = devtrace.reduce_dir(str(tmp_path))
    assert any(e.name == "bench.window" for e in t.host)
    assert t.devices == [] and t.busy_s() == 0.0
    ctx = _ctx([], 0.0, 1.0)
    ctx.trace = t
    assert ctx.idle_share() is None and ctx.roofline("_flash_kernel",
                                                     "flash") is None


def test_run_refuses_a_cpu_device():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(ROOT, "bench", "run.py"),
                        "--workload", "phi3-code", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, cwd=ROOT,
                       timeout=120)
    assert p.returncode != 0
    assert "correct" not in p.stdout
    assert "no TPU" in p.stderr
