#!/usr/bin/env python3
"""Smoke run of the bridge's device path on TPU, at published widths.

    python3 chip_smoke.py               # one chip: serving, reference, training
    python3 chip_smoke.py --four-chips  # four chips: 4 replicas, one per chip

One chip (the default):

  * serving — a ``BridgeService`` with one ``jaxlocal`` replica hosts
    phi3-mini-3.8b (bf16, published widths, Mosaic flash and decode kernels)
    in a ``ServingEngine`` (4 slots, 2048-token cache, 512-token prefill).
    8 requests of 64-512 prompt tokens, generated from ``--seed``, go
    through ``ServiceHandle.router()``; each must return 32 tokens;
  * reference — with the service stopped (two copies of the weights do not
    fit one chip): the kernels against ``repro.kernels.ref`` at the served
    shapes, and a teacher-forced forward of the XLA attention path over
    prompt + served tokens for two requests, whose greedy argmax must agree
    with the served tokens;
  * training — a ``BridgeJob`` runs ``train_job`` for 5 steps at phi3-mini
    widths with depth cut to 4 of 32 layers; it must reach DONE with finite
    losses, the first within 1.0 of ln(vocab).

Four chips (``--four-chips``, and nothing else): 4 replicas of the same
service, one per device, 16 requests through the router; each device must
hold one replica's weights, the router must use all 4, and one prompt sent
to every replica must come back with identical tokens.

Exits non-zero, without the result line, when JAX finds no TPU or any phase
fails.  The last line of a passing run is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import concurrent.futures as cf
import gc
import json
import math
import os
import sys
import time
import traceback

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

MODEL = {"preset": "published", "arch": "phi3-mini-3.8b",
         "config_overrides": {"attention_impl": "pallas"}}
ENGINE = {"max_batch": 4, "max_len": 2048, "prefill_len": 512}
PROMPT_TOKENS = (64, 512)   # prompt lengths are drawn from this range
MAX_NEW = 32
REQUESTS_ONE_CHIP = 8
REQUESTS_FOUR_CHIPS = 16
TRAIN = {"preset": "published", "arch": "phi3-mini-3.8b",
         "config_overrides": {"n_layers": 4},  # depth cut: 4 of 32 layers
         "steps": 5, "batch": 4, "seq": 512, "lr": 1e-4}

# thresholds of the checks against the plain reference
KERNEL_ATOL = 2e-2           # max |kernel - ref|, bf16 operands, f32 ref
TEACHER_FORCED_CASES = 2     # requests re-run through the reference
MIN_AGREEMENT = 0.95         # share of served tokens the reference agrees on
NEAR_TIE_STD = 0.05          # a served token within this many standard
#                              deviations of the reference's max logit agrees
FIRST_LOSS_TOL = 1.0         # |first training loss - ln(vocab)|

READY_TIMEOUT_S = 900.0
REQUEST_TIMEOUT_S = 600.0
TRAIN_TIMEOUT_S = 900.0


class SmokeFailure(Exception):
    pass


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# -- serving -------------------------------------------------------------------


def make_prompts(n: int, vocab: int, seed: int, lengths=PROMPT_TOKENS):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, size=int(rng.integers(lengths[0],
                                                         lengths[1] + 1))
                         ).tolist() for _ in range(n)]


def start_service(env, name: str, model: dict, engine: dict, replicas: int,
                  seed: int):
    """Submit a BridgeService of ``replicas`` jaxlocal serving replicas and
    wait until every one is ready (weights made, programs compiled)."""
    from repro.core import HealthProbeSpec

    script = json.dumps({"mode": "serve", **model, **engine, "seed": seed})
    spec = env.make_service_spec(
        "jaxlocal", replicas=replicas, script=script, updateinterval=0.5,
        # a replica makes its weights and compiles before it turns ready
        health=HealthProbeSpec(failure_threshold=5,
                               startup_failure_threshold=int(
                                   READY_TIMEOUT_S / 0.5)))
    t0 = time.perf_counter()
    handle = env.bridge.submit_service(name, spec)
    handle.wait_ready(timeout=READY_TIMEOUT_S)
    return handle, time.perf_counter() - t0


def engine_info(env, handle) -> list:
    jobs = env.clusters["jaxlocal"].jobs
    return [json.loads(jobs[e["job_id"]].outputs["engine.json"])
            for e in sorted(handle.endpoints(), key=lambda e: e["replica"])]


def send(router, prompts, max_new: int = MAX_NEW) -> list:
    """All prompts at once through the router: [(response, wall seconds)]."""
    def one(prompt):
        t0 = time.perf_counter()
        out = router.request({"prompt": prompt, "max_new_tokens": max_new})
        return out, time.perf_counter() - t0

    with cf.ThreadPoolExecutor(max_workers=len(prompts)) as pool:
        return list(pool.map(one, prompts))


def check_answers(answers, max_new: int = MAX_NEW) -> None:
    for i, (out, _) in enumerate(answers):
        check(len(out["tokens"]) == max_new,
              f"request {i} returned {len(out['tokens'])} tokens, "
              f"expected {max_new}")


def stop_service(env, handle) -> None:
    """Kill the service and wait until every replica's payload has returned,
    so that its weights can be freed."""
    from repro.core.backends.base import TERMINAL

    handle.cancel()
    handle.wait(timeout=120)
    jobs = env.clusters["jaxlocal"].jobs
    deadline = time.time() + 120
    while (any(j.state not in TERMINAL for j in list(jobs.values()))
           and time.time() < deadline):
        time.sleep(0.1)
    gc.collect()


def device_memory(dev) -> dict:
    return dev.memory_stats() or {}


# -- the plain reference -----------------------------------------------------


def kernel_check(cfg, engine: dict, seed: int) -> dict:
    """Mosaic kernels (``interpret=None``: Mosaic on a TPU) against the
    pure-jnp references at the served shapes; returns max |error|."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops, ref

    b, m, p = engine["max_batch"], engine["max_len"], engine["prefill_len"]
    h, hkv, d = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)

    def rnd(k, shape):
        return jax.random.normal(k, shape, jnp.float32).astype(jnp.bfloat16)

    def f32(x):
        return x.astype(jnp.float32)

    q, k, v = rnd(ks[0], (1, p, h, d)), rnd(ks[1], (1, p, hkv, d)), rnd(
        ks[2], (1, p, hkv, d))
    got = ops.flash_attention(q, k, v)
    with jax.default_matmul_precision("highest"):
        want = jnp.swapaxes(ref.flash_attention_ref(
            *(jnp.swapaxes(f32(t), 1, 2) for t in (q, k, v))), 1, 2)
    flash_err = float(jnp.max(jnp.abs(f32(got) - want)))

    qd = rnd(ks[3], (b, 1, h, d))
    ck, cv = rnd(ks[4], (b, m, hkv, d)), rnd(ks[5], (b, m, hkv, d))
    lengths = jax.random.randint(ks[6], (b,), 1, m + 1, jnp.int32)
    got = ops.decode_attention(qd, ck, cv, lengths)
    with jax.default_matmul_precision("highest"):
        want = ref.decode_attention_ref(
            f32(qd[:, 0]), jnp.swapaxes(f32(ck), 1, 2),
            jnp.swapaxes(f32(cv), 1, 2), lengths)[:, None]
    decode_err = float(jnp.max(jnp.abs(f32(got) - want)))
    return {"flash": flash_err, "decode": decode_err}


def teacher_forced(model: dict, engine: dict, seed: int, cases) -> dict:
    """Re-run (prompt, served tokens) pairs through a cache-free forward of
    the XLA attention path on the same seed weights; compare its greedy
    argmax at every generated position with the served token."""
    import jax
    import jax.numpy as jnp

    from repro.core.backends.jaxlocal import job_config
    from repro.models.transformer import forward_logits
    from repro.steps import init_model

    overrides = dict(model.get("config_overrides", {}), attention_impl="xla")
    cfg = job_config(dict(model, config_overrides=overrides))
    _, params = init_model(cfg, seed=seed, max_seq=engine["max_len"])
    width = engine["prefill_len"] + MAX_NEW
    fwd = jax.jit(lambda p, t: forward_logits(p, cfg, {"tokens": t})[0])
    exact = near = total = 0
    for prompt, served in cases:
        seq = list(prompt) + list(served[:-1])
        toks = np.zeros((1, width), np.int32)
        toks[0, :len(seq)] = seq
        start = len(prompt) - 1
        logits = np.asarray(
            fwd(params, jnp.asarray(toks))[0, start:start + len(served)],
            np.float32)
        served = np.asarray(served)
        gap = logits.max(-1) - logits[np.arange(len(served)), served]
        exact += int(np.sum(logits.argmax(-1) == served))
        near += int(np.sum(gap <= NEAR_TIE_STD * logits.std(-1)))
        total += len(served)
    del params
    return {"exact": exact / total, "agree": near / total, "tokens": total}


# -- phases ------------------------------------------------------------------


def serve_phase(model: dict = MODEL, engine: dict = ENGINE, seed: int = 0,
                n_requests: int = REQUESTS_ONE_CHIP,
                prompt_tokens=PROMPT_TOKENS) -> list:
    """One replica behind the router; returns [(prompt, served tokens)]."""
    import jax

    from repro.compat import kernel_path
    from repro.core import BridgeEnvironment
    from repro.core.backends.jaxlocal import job_config

    cfg = job_config(model)
    prompts = make_prompts(n_requests, cfg.vocab, seed, prompt_tokens)
    log(f"serve: {cfg.name} d_model={cfg.d_model} layers={cfg.n_layers} "
        f"heads={cfg.n_heads}/{cfg.n_kv_heads} head_dim="
        f"{cfg.resolved_head_dim} vocab={cfg.vocab} dtype={cfg.dtype} "
        f"attention={cfg.attention_impl}; engine {engine}")
    log(f"serve: {n_requests} prompts of {[len(p) for p in prompts]} tokens, "
        f"max_new_tokens={MAX_NEW}")
    with BridgeEnvironment(slots=2) as env:
        handle, ready_s = start_service(env, "phi3", model, engine, 1, seed)
        info = engine_info(env, handle)[0]
        log(f"serve: replica ready in {ready_s:.3f}s (weights "
            f"{info['init_s']:.3f}s, compile {info['compile_s']:.3f}s) on "
            f"device {info['device']} ({info['device_kind']})")
        log(f"serve: tpu_custom_call in compiled programs: {info['mosaic']}")
        check(kernel_path() != "mosaic"
              or (info["mosaic"]["prefill"] and info["mosaic"]["decode"]),
              "compiled prefill/decode hold no Mosaic kernel")
        router = handle.router(request_timeout=REQUEST_TIMEOUT_S)
        t0 = time.perf_counter()
        answers = send(router, prompts)
        wall = time.perf_counter() - t0
        check_answers(answers)
        for i, (out, dt) in enumerate(answers):
            log(f"serve: request {i}: {len(prompts[i])} prompt tokens -> "
                f"{len(out['tokens'])} tokens in {dt:.3f}s "
                f"(first 8: {out['tokens'][:8]})")
        log(f"serve: {n_requests}/{n_requests} answered with {MAX_NEW} "
            f"tokens each in {wall:.3f}s")
        mem = device_memory(jax.devices()[0])
        log(f"serve: peak_bytes_in_use={mem.get('peak_bytes_in_use')} "
            f"bytes_limit={mem.get('bytes_limit')}")
        stop_service(env, handle)
    mem = device_memory(jax.devices()[0])
    log(f"serve: service stopped; bytes_in_use={mem.get('bytes_in_use')}")
    return [(p, out["tokens"]) for p, (out, _) in zip(prompts, answers)]


def reference_phase(cases, model: dict = MODEL, engine: dict = ENGINE,
                    seed: int = 0) -> None:
    from repro.core.backends.jaxlocal import job_config

    cfg = job_config(model)
    errs = kernel_check(cfg, engine, seed)
    log(f"reference: kernel vs ref max|err| flash={errs['flash']:.6f} "
        f"decode={errs['decode']:.6f} (limit {KERNEL_ATOL})")
    check(errs["flash"] <= KERNEL_ATOL and errs["decode"] <= KERNEL_ATOL,
          f"kernel disagrees with ref: {errs}")
    tf = teacher_forced(model, engine, seed, cases[:TEACHER_FORCED_CASES])
    log(f"reference: teacher-forced XLA path over {tf['tokens']} served "
        f"tokens: argmax agreement {tf['exact']:.4f}, within "
        f"{NEAR_TIE_STD} std of the max {tf['agree']:.4f} "
        f"(limit {MIN_AGREEMENT})")
    check(tf["agree"] >= MIN_AGREEMENT,
          f"served tokens disagree with the reference: {tf}")


def train_phase(train: dict = TRAIN, seed: int = 0) -> list:
    from repro.core import DONE, BridgeEnvironment
    from repro.core.backends.jaxlocal import job_config
    from repro.models.params import count_params
    from repro.models.transformer import model_defs

    cfg = job_config(train)
    full = job_config(dict(train, config_overrides={}))
    n_params = count_params(model_defs(cfg, max_seq=train["seq"]))
    log(f"train: {cfg.name} d_model={cfg.d_model} vocab={cfg.vocab}, depth "
        f"cut to {cfg.n_layers} of {full.n_layers} layers "
        f"({n_params / 1e9:.3f} B parameters); {train['steps']} steps of "
        f"batch {train['batch']} x seq {train['seq']}")
    script = json.dumps({**train, "seed": seed, "workdir": "runs:smoke"})
    with BridgeEnvironment(slots=2) as env:
        spec = env.make_spec("jaxlocal", script=script, updateinterval=0.5,
                             jobproperties={"OutputFileName": "train.out"})
        t0 = time.perf_counter()
        handle = env.bridge.submit("train", spec)
        job = handle.wait(timeout=TRAIN_TIMEOUT_S)
        wall = time.perf_counter() - t0
        check(job.status.state == DONE,
              f"training job ended {job.status.state}: {job.status.message}")
        key = f"smoke/history_{job.status.job_id}.json"
        losses = json.loads(env.s3.get("runs", key))
    log(f"train: job DONE in {wall:.3f}s; losses {losses}")
    check(len(losses) == train["steps"],
          f"{len(losses)} losses for {train['steps']} steps")
    check(all(math.isfinite(x) for x in losses), f"non-finite loss {losses}")
    check(abs(losses[0] - math.log(cfg.vocab)) <= FIRST_LOSS_TOL,
          f"first loss {losses[0]} not within {FIRST_LOSS_TOL} of "
          f"ln({cfg.vocab})={math.log(cfg.vocab):.4f}")
    return losses


def four_chip_phase(model: dict = MODEL, engine: dict = ENGINE, seed: int = 0,
                    n_requests: int = REQUESTS_FOUR_CHIPS,
                    prompt_tokens=PROMPT_TOKENS) -> None:
    """4 replicas, one per device, behind one router."""
    import jax

    from repro.core import BridgeEnvironment
    from repro.core.backends.jaxlocal import job_config
    from repro.models.params import param_bytes
    from repro.models.transformer import model_defs

    devices = jax.devices()
    check(len(devices) == 4, f"--four-chips needs 4 devices, "
                             f"found {len(devices)}")
    cfg = job_config(model)
    weights = param_bytes(model_defs(cfg, max_seq=engine["max_len"]))
    prompts = make_prompts(n_requests, cfg.vocab, seed, prompt_tokens)
    with BridgeEnvironment(slots=4) as env:
        handle, ready_s = start_service(env, "phi3x4", model, engine, 4, seed)
        infos = engine_info(env, handle)
        log(f"four-chips: 4 replicas ready in {ready_s:.3f}s")
        for e, info in zip(sorted(handle.endpoints(),
                                  key=lambda e: e["replica"]), infos):
            log(f"four-chips: replica {e['replica']} job {e['job_id']} on "
                f"device {info['device']} (compile {info['compile_s']:.3f}s,"
                f" tpu_custom_call {info['mosaic']})")
        check(sorted(i["device"] for i in infos)
              == sorted(d.id for d in devices),
              f"replicas are not one per device: {infos}")
        for d in devices:
            used = device_memory(d).get("bytes_in_use")
            log(f"four-chips: device {d.id} bytes_in_use={used} "
                f"(one replica's weights: {weights})")
            if used is not None:
                check(weights <= used < 2 * weights,
                      f"device {d.id} does not hold exactly one replica's "
                      f"weights ({used} bytes in use)")
        router = handle.router(request_timeout=REQUEST_TIMEOUT_S)
        t0 = time.perf_counter()
        answers = send(router, prompts)
        wall = time.perf_counter() - t0
        check_answers(answers)
        by_job = {}
        for out, _ in answers:
            by_job[out["served_by"]] = by_job.get(out["served_by"], 0) + 1
        log(f"four-chips: {n_requests}/{n_requests} answered with {MAX_NEW} "
            f"tokens each in {wall:.3f}s; requests per replica job {by_job}")
        check(len(by_job) == 4, f"router used {len(by_job)} of 4 replicas")
        cluster = env.clusters["jaxlocal"]
        probe = {"prompt": prompts[0], "max_new_tokens": MAX_NEW}
        tokens = []
        for e in sorted(handle.endpoints(), key=lambda e: e["replica"]):
            status, body = cluster.serve_invoke(e["job_id"], probe)
            check(status == 200, f"replica {e['replica']} answered {status}")
            tokens.append(body["tokens"])
            log(f"four-chips: probe of {len(prompts[0])} tokens on replica "
                f"{e['replica']} (device {body['device']}): {body['tokens']}")
        check(all(t == tokens[0] for t in tokens),
              "replicas disagree on the same prompt")
        log("four-chips: all 4 replicas return identical tokens")
        stop_service(env, handle)


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--four-chips", action="store_true",
                   help="run only the four-replica phase on four chips")
    args = p.parse_args()

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        log(f"no TPU: JAX's first device is {dev.platform!r}")
        return 2
    from importlib import metadata

    from repro.launch.compile_cache import enable_compile_cache

    versions = {}
    for pkg in ("jax", "jaxlib", "libtpu"):
        try:
            versions[pkg] = metadata.version(pkg)
        except metadata.PackageNotFoundError:
            versions[pkg] = "not installed"
    log(f"device_kind={dev.device_kind!r} count={len(devices)} "
        + " ".join(f"{k}={v}" for k, v in versions.items()))
    log(f"compile cache: {enable_compile_cache()}")
    t0 = time.perf_counter()
    try:
        if args.four_chips:
            four_chip_phase(seed=args.seed)
        else:
            cases = serve_phase(seed=args.seed)
            reference_phase(cases, seed=args.seed)
            train_phase(seed=args.seed)
    except Exception as e:  # noqa: BLE001 - any phase failure fails the run
        traceback.print_exc()
        log(f"FAILED: {type(e).__name__}: {e}")
        return 1
    log(f"all phases passed in {time.perf_counter() - t0:.3f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
