"""The plain reference a served model is compared with, and its control.

The reference is a dense pre-norm decoder written from the configuration
file alone, in float32 with ``precision=highest`` and with no kernel, no
cache and no batching: RMSNorm, rotary embeddings (half rotation), causal
grouped-query attention, a SiLU-gated MLP, an untied output head.  It runs
teacher-forced over a request's prompt and served tokens.

Its weights are made here from the seed, by the same rule the program uses
to make its own (``weight_leaves``): one key per leaf, split from
``PRNGKey(seed)`` in the leaves' sorted-path order; a matmul leaf is a
truncated normal in [-2, 2] over sqrt(fan-in), the embedding a truncated
normal, the norm scales ones; each cast to its stored type.  Nothing the
program made is read.

The comparison reads, at each served token, the gap by which its logit
lies below the reference's best at that position.  The control puts the
reference in the program's place at the precision below the configured
bfloat16: int8, every weight matmul with per-output-channel weight scales
and per-token activation scales, accumulated in int32 (attention stays in
float32).  Its gap is read at the token the int8 logits put first.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.work import Shape

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
BUCKET = 512  # sequences are right-padded to a multiple of this
ROWS = 128    # and the compared positions to a multiple of this, so that
#               runs share a few compiled programs


def weight_leaves(s: Shape, dtype) -> List[Tuple[str, tuple, str, int, object]]:
    """(name, shape, init, fan-in, dtype) of every leaf, in the order the
    program flattens its parameter tree (sorted paths)."""
    L, d, H, K, D, F, V = (s.layers, s.d, s.heads, s.kv_heads, s.head_dim,
                           s.d_ff, s.vocab)
    return [
        ("wk", (L, d, K, D), "normal", d, dtype),
        ("wo", (L, H, D, d), "normal", H * D, dtype),
        ("wq", (L, d, H, D), "normal", d, dtype),
        ("wv", (L, d, K, D), "normal", d, dtype),
        ("ln_attn", (L, d), "ones", 0, F32),
        ("ln_mlp", (L, d), "ones", 0, F32),
        ("w1", (L, d, F), "normal", d, dtype),
        ("w2", (L, F, d), "normal", F, dtype),
        ("w3", (L, d, F), "normal", d, dtype),
        ("embedding", (V, d), "embed", 0, dtype),
        ("lm_head", (d, V), "normal", d, dtype),
        ("ln_f", (d,), "ones", 0, F32),
    ]


def make_weights(cfg: dict, seed: int) -> Dict[str, jax.Array]:
    """All weights of the configuration under ``seed``, made on the device
    in one jitted call, in their stored types."""
    if cfg.get("tie_word_embeddings"):
        raise NotImplementedError("the reference has an untied head")
    leaves = weight_leaves(Shape.of(cfg), jnp.dtype(cfg["torch_dtype"]))

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


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = jnp.exp(-jnp.log(theta) * jnp.arange(half, dtype=F32) / half)
    ang = pos.astype(F32)[:, None, None] * freqs
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], axis=-1)


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


def forward(w, toks, rows, *, shape: Shape, eps: float, theta: float,
            dense=dense_f32):
    """Logits (R, V) at ``rows`` of one right-padded sequence ``toks``."""
    H, K, D = shape.heads, shape.kv_heads, shape.head_dim
    S = toks.shape[0]
    pos = jnp.arange(S)
    causal = pos[None, :] <= pos[:, None]
    x = w["embedding"][toks].astype(F32)

    def layer(x, lw):
        lw = jax.tree_util.tree_map(lambda a: a.astype(F32), lw)
        h = _rms(x, lw["ln_attn"], eps)
        q = dense(h, lw["wq"].reshape(-1, H * D)).reshape(S, H, D)
        k = dense(h, lw["wk"].reshape(-1, K * D)).reshape(S, K, D)
        v = dense(h, lw["wv"].reshape(-1, K * D)).reshape(S, K, D)
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
        q = q.reshape(S, K, H // K, D)
        sc = jnp.einsum("qkgd,skd->kgqs", q, k, precision=HIGHEST) / np.sqrt(D)
        p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        a = jnp.einsum("kgqs,skd->qkgd", p, v, precision=HIGHEST)
        x = x + dense(a.reshape(S, H * D), lw["wo"].reshape(H * D, -1))
        h = _rms(x, lw["ln_mlp"], eps)
        m = jax.nn.silu(dense(h, lw["w1"])) * dense(h, lw["w3"])
        return x + dense(m, lw["w2"]), None

    blocks = {n: w[n] for n in ("wq", "wk", "wv", "wo", "ln_attn", "ln_mlp",
                                "w1", "w2", "w3")}
    x, _ = jax.lax.scan(layer, x, blocks)
    x = _rms(x[rows], w["ln_f"].astype(F32), eps)
    return dense(x, w["lm_head"].astype(F32))


@functools.lru_cache(maxsize=None)
def _programs(shape: Shape, eps: float, theta: float):
    """Jitted (reference gaps, control gaps) over one padded sequence."""
    def ref_gaps(w, toks, rows, served):
        ref = forward(w, toks, rows, shape=shape, eps=eps, theta=theta)
        best = jnp.max(ref, axis=-1)
        return best - jnp.take_along_axis(ref, served[:, None], 1)[:, 0]

    def ctrl_gaps(w, toks, rows, served):
        ref = forward(w, toks, rows, shape=shape, eps=eps, theta=theta)
        low = forward(w, toks, rows, shape=shape, eps=eps, theta=theta,
                      dense=dense_int8)
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


def gaps(cfg: dict, seed: int, cases: Sequence[Tuple[Sequence[int],
                                                     Sequence[int]]],
         control: bool = False) -> Dict[str, np.ndarray]:
    """Per served token of each (prompt, served tokens) case, the gap below
    the reference's best logit; with ``control``, also the gap of the token
    the int8 control puts first."""
    shape = Shape.of(cfg)
    ref_fn, ctrl_fn = _programs(shape, float(cfg["rms_norm_eps"]),
                                float(cfg["rope_theta"]))
    w = make_weights(cfg, seed)
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
