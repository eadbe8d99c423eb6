"""The bridge's device path at smoke size on the CPU.

Covers what ``chip_smoke.py`` drives on the chip: the ``jaxlocal`` job-script
presets, donation of the state each step replaces, the per-cluster device
pool, the compile-cache helper and the peak table, and the smoke script's own
phases (serving through a ``BridgeService``, the plain reference, bridged
training, four replicas on four virtual devices).
"""
import importlib.util
import json
import math
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.backends import jaxlocal as JX

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SMOKE_MODEL = {"preset": "smoke", "arch": "phi3-mini-3.8b",
               "config_overrides": {"attention_impl": "pallas"}}
SMOKE_ENGINE = {"max_batch": 4, "max_len": 96, "prefill_len": 64}
SMOKE_PROMPTS = (8, 64)


def _chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# -- job-script presets --------------------------------------------------------


def test_job_config_presets():
    smoke = JX.job_config({"arch": "phi3-mini-3.8b"})
    assert smoke.name == "phi3-mini-smoke"
    full = JX.job_config({"arch": "phi3-mini-3.8b", "preset": "published",
                          "config_overrides": {"n_layers": 4}})
    assert (full.d_model, full.n_heads, full.vocab) == (3072, 32, 32064)
    assert full.n_layers == 4 and full.dtype == "bfloat16"
    with pytest.raises(ValueError, match="preset"):
        JX.job_config({"arch": "phi3-mini-3.8b", "preset": "huge"})


def test_attention_projections_init_from_true_fan_in():
    """wq/wk/wv (d, H, D) sum over d inputs and wo (H, D, d) over H*D: their
    std is 1/sqrt(that), not 1/sqrt of the second-to-last dim.  Too large a
    std made attention one-hot and the full-width model's tokens a function
    of rounding noise."""
    from repro.configs.base import get_config
    from repro.steps import init_model

    cfg = get_config("phi3-mini-3.8b", n_layers=1, d_model=256, n_heads=32,
                     n_kv_heads=32, d_ff=256, vocab=512)
    _, params = init_model(cfg, max_seq=16)
    attn = params["blocks"]["attn"]
    trunc_std = 0.8796  # std of a normal truncated to +-2
    for name, fan_in in (("wq", 256), ("wk", 256), ("wv", 256),
                         ("wo", 32 * 8)):
        got = float(jnp.std(attn[name].astype(jnp.float32)))
        np.testing.assert_allclose(got, trunc_std / math.sqrt(fan_in),
                                   rtol=0.05, err_msg=name)


# -- donation ----------------------------------------------------------------


def test_engine_donates_the_kv_cache():
    from repro.configs.base import get_smoke_config
    from repro.serving import ServingEngine
    from repro.steps import init_model

    cfg = get_smoke_config("phi3-mini-3.8b")
    _, params = init_model(cfg, max_seq=48)
    eng = ServingEngine(cfg, params, max_batch=2, max_len=48, prefill_len=8)
    before = eng.cache["k"]
    eng.submit([1, 2, 3], max_new_tokens=3)
    eng.step()  # one insert, one decode: both replace the cache in place
    assert before.is_deleted()
    assert not eng.cache["k"].is_deleted()
    assert set(eng.programs) == {"prefill", "insert", "decode"}


def test_train_step_donates_params_and_opt_state():
    from repro.configs.base import get_smoke_config
    from repro.optim import AdamWConfig, adamw_init
    from repro.steps import init_model

    cfg = get_smoke_config("phi3-mini-3.8b")
    _, params = init_model(cfg, max_seq=16)
    opt = adamw_init(params)
    step = JX.make_train_step(cfg, AdamWConfig(total_steps=2))
    toks = jnp.ones((2, 16), jnp.int32)
    batch = {"tokens": toks, "targets": toks,
             "mask": jnp.ones((2, 16), jnp.float32)}
    p_leaf = jax.tree_util.tree_leaves(params)[0]
    o_leaf = jax.tree_util.tree_leaves(opt)[0]
    new_p, _, metrics = step(params, opt, batch)
    assert p_leaf.is_deleted() and o_leaf.is_deleted()
    assert math.isfinite(float(metrics["loss"]))
    assert not jax.tree_util.tree_leaves(new_p)[0].is_deleted()


# -- device pool ---------------------------------------------------------------


def test_device_pool_one_device_shares_it():
    pool = JX.DevicePool()
    with pool.lease("a") as d1, pool.lease("b") as d2:
        assert d1 == d2 == jax.local_devices()[0]
        assert set(pool.leases) == {"a", "b"}
        assert jnp.zeros(2).devices() == {d1}
    assert pool.leases == {}


def test_jaxlocal_cluster_builds_without_touching_devices():
    from repro.core.objectstore import ObjectStore

    cluster = JX.make_jaxlocal_cluster(ObjectStore())
    try:
        assert cluster.devices._devices is None  # listed at first lease
    finally:
        cluster.shutdown()


# -- compile cache -------------------------------------------------------------


def test_compile_cache_honours_env(monkeypatch, tmp_path):
    from repro.launch import compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # JAX reads env


def test_compile_cache_default_is_fixed_in_checkout(monkeypatch):
    from repro.launch import compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        got = compile_cache.enable_compile_cache()
        assert got == os.path.join(ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_compile_cache_lands_in_env_dir(tmp_path):
    """A launcher with JAX_COMPILATION_CACHE_DIR set writes its compiled
    programs there."""
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--arch",
         "phi3-mini-3.8b", "--requests", "1", "--max-new", "2", "--json"],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert json.loads(out.stdout.strip().splitlines()[-1])["completed"] == 1
    assert any(tmp_path.iterdir())


# -- peak table ------------------------------------------------------------------


def test_peak_table_keyed_by_device_kind():
    from repro.launch.mesh import peaks

    v5e = peaks("TPU v5 lite")
    assert v5e["peak_flops_bf16"] == 197e12 and v5e["hbm_bw"] == 819e9
    with pytest.raises(KeyError, match="no peak table"):
        peaks("cpu")


# -- chip_smoke.py phases at smoke size ------------------------------------------


def test_chip_smoke_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "chip_smoke.py"], env=env,
                         cwd=ROOT, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_chip_smoke_phases_at_smoke_size():
    cs = _chip_smoke()
    cases = cs.serve_phase(SMOKE_MODEL, SMOKE_ENGINE, seed=0,
                           prompt_tokens=SMOKE_PROMPTS)
    assert len(cases) == cs.REQUESTS_ONE_CHIP
    assert all(len(toks) == cs.MAX_NEW for _, toks in cases)
    cs.reference_phase(cases, SMOKE_MODEL, SMOKE_ENGINE, seed=0)
    losses = cs.train_phase({"preset": "smoke", "arch": "phi3-mini-3.8b",
                             "config_overrides": {}, "steps": 3, "batch": 2,
                             "seq": 32, "lr": 1e-4}, seed=0)
    assert len(losses) == 3


def test_chip_smoke_reference_catches_wrong_tokens():
    cs = _chip_smoke()
    cfg = JX.job_config(SMOKE_MODEL)
    prompt = cs.make_prompts(1, cfg.vocab, 0, SMOKE_PROMPTS)[0]
    wrong = [(t * 7 + 3) % cfg.vocab for t in range(cs.MAX_NEW)]
    tf = cs.teacher_forced(SMOKE_MODEL, SMOKE_ENGINE, 0, [(prompt, wrong)])
    assert tf["agree"] < cs.MIN_AGREEMENT


def test_chip_smoke_four_replicas_on_four_virtual_devices():
    """The --four-chips phase on 4 CPU devices: one replica per device, all
    used by the router, identical tokens for one prompt."""
    code = ("import sys, json; sys.path.insert(0, '.'); import chip_smoke as cs; "
            f"cs.four_chip_phase({SMOKE_MODEL!r}, {SMOKE_ENGINE!r}, seed=0, "
            f"prompt_tokens={SMOKE_PROMPTS!r})")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(ROOT, "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, (out.stdout[-3000:], out.stderr[-3000:])
    assert "all 4 replicas return identical tokens" in out.stdout
    devices = {line.split(" on device ")[1].split()[0]
               for line in out.stdout.splitlines()
               if "four-chips: replica" in line}
    assert devices == {"0", "1", "2", "3"}
