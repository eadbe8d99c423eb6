"""The phi3 family (``model_type`` "phi3"): a dense pre-norm decoder, its
reference forward, its seeded weights and its useful work.

The reference is written from the configuration file alone, in float32
with ``precision=highest`` and with no kernel, no cache and no batching:
RMSNorm, rotary embeddings (half rotation), causal grouped-query attention,
a SiLU-gated MLP, an untied output head.

Useful work is counted from the configuration and the requests' own
lengths only: never padded positions, never the cache's ``max_len``, never
empty slots.  A served request with ``P`` prompt tokens that returned
``O`` tokens is, as model work, one causal forward over ``P + O - 1``
tokens (its prompt, then every generated token but the last fed back) with
the output head at the ``O`` positions whose token was served.  Positions
``0 .. P-1`` are the prefill's (the flash kernel's); positions
``P .. P+O-2`` are decode steps, and the one at position ``i`` attends
over ``i + 1`` cached keys.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.work import BF16, causal_pairs

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class Shape:
    """A dense decoder's sizes, read from a configuration file's
    Hugging Face-style keys."""
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int

    @classmethod
    def of(cls, cfg: dict) -> "Shape":
        heads = cfg["num_attention_heads"]
        return cls(layers=cfg["num_hidden_layers"], d=cfg["hidden_size"],
                   heads=heads, kv_heads=cfg["num_key_value_heads"],
                   head_dim=cfg.get("head_dim") or cfg["hidden_size"] // heads,
                   d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"])

    @property
    def layer_params(self) -> int:
        """Matmul weights of one block: q, k, v, o and the gated MLP."""
        hd = self.head_dim
        return (self.d * self.heads * hd + 2 * self.d * self.kv_heads * hd
                + self.heads * hd * self.d + 3 * self.d * self.d_ff)

    @property
    def head_params(self) -> int:
        return self.d * self.vocab


# -- weights ---------------------------------------------------------------


def weight_leaves(cfg: dict) -> List[Tuple[str, tuple, str, int, object]]:
    """(name, shape, init, fan-in, dtype) of every leaf, in the order the
    program flattens its parameter tree (sorted paths)."""
    if cfg.get("tie_word_embeddings"):
        raise NotImplementedError("the reference has an untied head")
    s, dtype = Shape.of(cfg), jnp.dtype(cfg["torch_dtype"])
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


# -- the reference forward -------------------------------------------------


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


def forward(w, toks, rows, cfg: dict, dense):
    """Logits (R, V) at ``rows`` of one right-padded sequence ``toks``;
    ``dense(x, w)`` is every weight matmul."""
    shape = Shape.of(cfg)
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
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


# -- useful work -----------------------------------------------------------


def request_flops(shape: Shape, prompt: int, out: int) -> float:
    """Useful model FLOPs of one served request: every weight matmul at
    ``P + O - 1`` positions, the head at ``O`` and causal attention (QK and
    PV, 4 FLOPs per query-key pair per head dim) at each position's own
    context."""
    tokens = prompt + out - 1
    attn = 4 * shape.heads * shape.head_dim * causal_pairs(0, tokens - 1)
    return float(2 * shape.layers * shape.layer_params * tokens
                 + 2 * shape.head_params * out + shape.layers * attn)


def flash_work(shape: Shape, prompt: int) -> Tuple[float, float]:
    """(FLOPs, bytes) the prefill attention kernel needs for one prompt of
    ``prompt`` real tokens, over all layers: causal QK and PV, and reading
    Q, K, V and writing O once."""
    flops = 4 * shape.heads * shape.head_dim * causal_pairs(0, prompt - 1)
    io = (2 * shape.heads + 2 * shape.kv_heads) * shape.head_dim * prompt
    return float(shape.layers * flops), float(shape.layers * io * BF16)


def decode_attn_work(shape: Shape, prompt: int, out: int
                     ) -> Tuple[float, float]:
    """(FLOPs, bytes) the decode attention kernel needs for one request's
    useful decode steps, over all layers: at positions ``P .. P+O-2`` it
    reads the K and V of the valid context (``i + 1`` positions) and does
    QK and PV against it."""
    pairs = causal_pairs(prompt, prompt + out - 2)
    flops = 4 * shape.heads * shape.head_dim * pairs
    kv = 2 * shape.kv_heads * shape.head_dim * pairs * BF16
    return float(shape.layers * flops), float(shape.layers * kv)


def request_work(cfg: dict, prompt: int, out: int) -> Dict[str, float]:
    """The useful work of one served request: ``flops`` of the whole model,
    and the FLOPs and bytes of the flash (prefill) and decode attention
    kernels."""
    shape = Shape.of(cfg)
    flash_flops, flash_bytes = flash_work(shape, prompt)
    decode_flops, decode_bytes = decode_attn_work(shape, prompt, out)
    return {"flops": request_flops(shape, prompt, out),
            "flash_flops": flash_flops, "flash_bytes": flash_bytes,
            "decode_flops": decode_flops, "decode_bytes": decode_bytes}
