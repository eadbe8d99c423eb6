"""The plain reference a served model is compared with, and its control:
the parts that belong to no model family.

A configuration's family (its ``model_type``) is a module
``bench/models/<model_type>.py`` (``manifest.family``) that gives the
reference's forward (``forward(w, toks, rows, cfg, dense)``: teacher-forced
float32 logits with ``precision=highest``, no kernel, no cache and no
batching) and its weight leaves (``weight_leaves(cfg)``).  It runs over a
request's prompt and served tokens.

Its weights are made here from the seed, by the same rule the program uses
to make its own: one key per leaf, split from ``PRNGKey(seed)`` in the
leaves' sorted-path order; a matmul leaf is a truncated normal in [-2, 2]
over sqrt(fan-in), the embedding a truncated normal, the norm scales ones;
each cast to its stored type.  Nothing the program made is read.

The comparison reads, at each served token, the gap by which its logit
lies below the reference's best at that position.  The control puts the
reference in the program's place at the precision below the configured
bfloat16: int8, every weight matmul (the family's ``dense``) with
per-output-channel weight scales and per-token activation scales,
accumulated in int32 (attention stays in float32).  Its gap is read at the
token the int8 logits put first.
"""
from __future__ import annotations

import functools
import json
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
BUCKET = 512  # sequences are right-padded to a multiple of this
ROWS = 128    # and the compared positions to a multiple of this, so that
#               runs share a few compiled programs


def make_weights(family, cfg: dict, seed: int) -> Dict[str, jax.Array]:
    """All weights of the configuration (``family.weight_leaves``) under
    ``seed``, made on the device in one jitted call, in their stored
    types."""
    leaves = family.weight_leaves(cfg)

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(leaves))
        out = {}
        for k, (name, shape, init, fan_in, dt) in zip(keys, leaves):
            if init == "ones":
                out[name] = jnp.ones(shape, dt)
                continue
            x = jax.random.truncated_normal(k, -2.0, 2.0, shape, F32)
            if init == "normal":
                x = x * np.float32(1.0 / np.sqrt(fan_in))
            out[name] = x.astype(dt)
        return out

    return make(jax.random.PRNGKey(seed))


def dense_f32(x, w):
    return jnp.matmul(x, w, precision=HIGHEST)


def dense_int8(x, w):
    """int8 x int8 -> int32, per-token and per-output-channel scales."""
    def quant(a, axis):
        scale = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / 127.0
        scale = jnp.where(scale == 0, 1.0, scale)
        return jnp.round(a / scale).astype(jnp.int8), scale

    xq, sx = quant(x, 1)
    wq, sw = quant(w, 0)
    y = jax.lax.dot(xq, wq, preferred_element_type=jnp.int32)
    return y.astype(F32) * sx * sw


@functools.lru_cache(maxsize=None)
def _programs(family, cfg_json: str):
    """Jitted (reference gaps, control gaps) over one padded sequence."""
    cfg = json.loads(cfg_json)

    def ref_gaps(w, toks, rows, served):
        ref = family.forward(w, toks, rows, cfg, dense_f32)
        best = jnp.max(ref, axis=-1)
        return best - jnp.take_along_axis(ref, served[:, None], 1)[:, 0]

    def ctrl_gaps(w, toks, rows, served):
        ref = family.forward(w, toks, rows, cfg, dense_f32)
        low = family.forward(w, toks, rows, cfg, dense_int8)
        first = jnp.argmax(low, axis=-1)
        best = jnp.max(ref, axis=-1)
        return (best - jnp.take_along_axis(ref, served[:, None], 1)[:, 0],
                best - jnp.take_along_axis(ref, first[:, None], 1)[:, 0])

    return jax.jit(ref_gaps), jax.jit(ctrl_gaps)


def _padded(prompt: Sequence[int], tokens: Sequence[int], rows_pad: int):
    seq = list(prompt) + list(tokens[:-1])
    n = -(-len(seq) // BUCKET) * BUCKET
    toks = np.zeros(n, np.int32)
    toks[:len(seq)] = seq
    o = len(tokens)
    rows = np.zeros(rows_pad, np.int32)
    rows[:o] = len(prompt) - 1 + np.arange(o)
    served = np.zeros(rows_pad, np.int32)
    served[:o] = tokens
    return toks, rows, served, o


def gaps(family, cfg: dict, seed: int,
         cases: Sequence[Tuple[Sequence[int], Sequence[int]]],
         control: bool = False) -> Dict[str, np.ndarray]:
    """Per served token of each (prompt, served tokens) case, the gap below
    the reference's best logit; with ``control``, also the gap of the token
    the int8 control puts first."""
    ref_fn, ctrl_fn = _programs(family, json.dumps(cfg, sort_keys=True))
    w = make_weights(family, cfg, seed)
    rows_pad = -(-max(len(t) for _, t in cases) // ROWS) * ROWS
    out: Dict[str, list] = {"served": [], "control": []}
    for prompt, tokens in cases:
        toks, rows, served, o = _padded(prompt, tokens, rows_pad)
        if control:
            g, c = ctrl_fn(w, toks, rows, served)
            out["control"].append(np.asarray(c)[:o])
        else:
            g = ref_fn(w, toks, rows, served)
        out["served"].append(np.asarray(g)[:o])
    del w
    return {k: np.concatenate(v) if v else np.zeros(0)
            for k, v in out.items()}


def sample(done: Sequence[Tuple[Sequence[int], Sequence[int]]], seed: int,
           min_tokens: int) -> List[int]:
    """Indices into ``done`` (prompt, served tokens) to compare: the longest
    request (most served tokens, then longest prompt), then others in an
    order drawn from the seed until ``min_tokens`` served tokens are in."""
    if not done:
        return []
    longest = max(range(len(done)),
                  key=lambda i: (len(done[i][1]), len(done[i][0])))
    order = [longest] + [int(i) for i in np.random.default_rng(
        [7, seed]).permutation(len(done)) if i != longest]
    picked, n = [], 0
    for i in order:
        if n >= min_tokens:
            break
        picked.append(i)
        n += len(done[i][1])
    return picked
