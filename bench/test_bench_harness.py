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


def test_a_cell_of_new_files_is_found_by_name(tmp_path):
    """A later change adds a cell, its configuration, its mix and a metric as
    new files plus entries: the harness finds all of them by name."""
    bench = json.loads(json.dumps(BENCH))
    (tmp_path / "bench" / "configs").mkdir(parents=True)
    (tmp_path / "bench" / "traffic").mkdir()
    (tmp_path / "bench" / "metrics").mkdir()
    (tmp_path / "bench" / "configs" / "tiny-model.json").write_text(
        json.dumps({"hidden_size": 8, "vocab_size": 16}))
    (tmp_path / "bench" / "traffic" / "burst.json").write_text(
        json.dumps({"loop": "open", "rate_rps": 3.0,
                    "prompt_tokens": [4, 8], "output_tokens": [2, 4]}))
    (tmp_path / "bench" / "metrics" / "queue_wait_ms.lat.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    bench["configs"].append({"name": "tiny-model", "source": "x",
                             "file": "bench/configs/tiny-model.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "tiny-burst", "config": "tiny-model",
                               "traffic": "burst", "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "queue_wait_ms.lat", "unit": "ms",
                               "better": "lower", "source": "host_clock",
                               "layer": "router", "moves": "latency_p90_ms",
                               "workloads": ["tiny-burst"]})
    bench["end_to_end"].append({"name": "latency_p90_ms", "unit": "ms",
                                "better": "lower", "bound": 0.1,
                                "source": "host_clock",
                                "workloads": ["tiny-burst"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = manifest.cell("tiny-burst", root=str(tmp_path))
    assert cell.config["hidden_size"] == 8
    assert cell.traffic["rate_rps"] == 3.0
    assert [m["name"] for m in cell.per_layer] == ["queue_wait_ms.lat"]
    got = manifest.read_metrics(cell.per_layer, None, root=str(tmp_path))
    assert got == {"queue_wait_ms.lat": {"value": 42.0, "unit": "ms"}}
    with pytest.raises(KeyError):
        manifest.cell("no-such-cell", root=str(tmp_path))


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
    shape = work.Shape(layers=1, d=8, heads=2, kv_heads=1, head_dim=4,
                       d_ff=16, vocab=32)
    return Context(loop=loop, outcomes=outcomes, t0=t0, t_end=t_end,
                   setup_s=1.0, shape=shape, peak=work.PEAKS["TPU v5 lite"])


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

PHI3 = work.Shape.of(manifest.cell("phi3-code").config)
# grouped-query attention: granite-3-8b's published widths at 20 layers
GRANITE = work.Shape.of({"num_hidden_layers": 20, "hidden_size": 4096,
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
    flops, nbytes = work.decode_attn_work(PHI3, 100, 3)
    assert nbytes == 32 * 2 * 32 * 96 * (101 + 102) * 2
    assert flops == 32 * 4 * 32 * 96 * (101 + 102)
    # granite: GQA reads 8 of 32 heads' K/V, 20 layers; one token: no
    # decode step is useful (the first token comes with the prompt)
    assert work.decode_attn_work(GRANITE, 2000, 1) == (0.0, 0.0)
    flops, nbytes = work.decode_attn_work(GRANITE, 2000, 2)
    assert nbytes == 20 * 2 * 8 * 128 * 2001 * 2
    assert flops == 20 * 4 * 32 * 128 * 2001


def test_causal_prefill_flops_and_bytes():
    # granite, prompt 1024: sum of 1..1024 = 524,800 query-key pairs per
    # head, 4 FLOPs per pair per head dim
    flops, nbytes = work.flash_work(GRANITE, 1024)
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
    assert work.request_flops(PHI3, 2, 2) == want


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
