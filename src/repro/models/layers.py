"""Core layers: norms, RoPE, GQA attention (train/prefill/decode), MLPs.

All layers follow the same convention: ``<layer>_defs(cfg)`` returns a pytree
of ParamDef; ``<layer>(params, x, ...)`` applies it.  Compute-sensitive
reductions (softmax, norms) run in f32 and cast back to the activation dtype.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.params import ParamDef

Params = Dict[str, Any]


def adtype(cfg) -> Any:
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def norm_defs(cfg, d: Optional[int] = None) -> Params:
    d = d or cfg.d_model
    out = {"scale": ParamDef((d,), ("embed",), init="ones", dtype=jnp.float32)}
    if cfg.norm == "layernorm":
        out["bias"] = ParamDef((d,), ("embed",), init="zeros", dtype=jnp.float32)
    return out


def apply_norm(p: Params, x: jax.Array, kind: str) -> jax.Array:
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + 1e-6) * p["scale"]
    elif kind == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + 1e-5) * p["scale"] + p["bias"]
    else:
        raise ValueError(kind)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# Rotary position embeddings (llama-style half-rotation)
# ---------------------------------------------------------------------------


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, S, H, D); positions: (B, S) int32."""
    if theta <= 0:
        return x
    d = x.shape[-1]
    half = d // 2
    freqs = jnp.exp(-jnp.log(theta) * jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs  # (B,S,half)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate([xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention (GQA / MQA), full + windowed + decode-over-cache
# ---------------------------------------------------------------------------


def attention_defs(cfg) -> Params:
    d, h = cfg.d_model, cfg.resolved_head_dim
    dt = adtype(cfg)
    return {
        "wq": ParamDef((d, cfg.n_heads, h), ("embed", "heads", "head_dim"), dtype=dt,
                       fan_in=d),
        "wk": ParamDef((d, cfg.n_kv_heads, h), ("embed", "kv_heads", "head_dim"),
                       dtype=dt, fan_in=d),
        "wv": ParamDef((d, cfg.n_kv_heads, h), ("embed", "kv_heads", "head_dim"),
                       dtype=dt, fan_in=d),
        "wo": ParamDef((cfg.n_heads, h, d), ("heads", "head_dim", "embed"), dtype=dt,
                       fan_in=cfg.n_heads * h),
    }


def cross_attention_defs(cfg) -> Params:
    return attention_defs(cfg)


def _gqa_scores(q: jax.Array, k: jax.Array, n_rep: int) -> jax.Array:
    """q: (B,Sq,Hq,D), k: (B,Sk,Hkv,D) -> scores (B,Hkv,G,Sq,Sk)."""
    b, sq, hq, d = q.shape
    hkv = k.shape[2]
    qg = q.reshape(b, sq, hkv, n_rep, d)
    return jnp.einsum("bqhgd,bkhd->bhgqk", qg, k)


def _gqa_out(w: jax.Array, v: jax.Array) -> jax.Array:
    """w: (B,Hkv,G,Sq,Sk), v: (B,Sk,Hkv,D) -> (B,Sq,Hq,D)."""
    b, hkv, g, sq, sk = w.shape
    out = jnp.einsum("bhgqk,bkhd->bqhgd", w, v)
    return out.reshape(b, sq, hkv * g, out.shape[-1])


def _masked_softmax(scores: jax.Array, mask: jax.Array, dtype) -> jax.Array:
    scores = jnp.where(mask, scores.astype(jnp.float32), -1e30)
    return jax.nn.softmax(scores, axis=-1).astype(dtype)


def _blockwise_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                         n_rep: int, hd: int, window: int,
                         block_q: int, unroll: bool = False) -> jax.Array:
    """q-chunked causal attention (XLA flash stand-in): the (S,S) score
    matrix never materializes — per chunk only (B,Hkv,G,bq,S) lives.
    Matches the Pallas kernel's memory behaviour in a form the dry-run can
    lower on any backend."""
    b, s, hq, d = q.shape
    bq = min(block_q, s)
    pad = (-s) % bq
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nb = q.shape[1] // bq
    qc = q.reshape(b, nb, bq, hq, d).transpose(1, 0, 2, 3, 4)  # (nb,B,bq,H,D)
    kt = jax.lax.broadcasted_iota(jnp.int32, (1, s), 1)

    def chunk(ci, qb):
        # qb: (B,bq,Hq,D); rows are global positions ci*bq + i
        scores = _gqa_scores(qb, k, n_rep) / jnp.sqrt(hd).astype(jnp.float32)
        rows = ci * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
        mask = kt <= rows  # (bq, S)
        if window > 0:
            mask &= kt > rows - window
        w = _masked_softmax(scores, mask, qb.dtype)
        return _gqa_out(w, v)  # (B,bq,Hq,D)

    if unroll:
        # python-unrolled chunk loop: identical numerics; every chunk's ops
        # are explicit in HLO so cost_analysis counts them (a lax.scan body
        # is visited ONCE by XLA's cost analysis — see dryrun.py probes)
        out = jnp.stack([chunk(ci, qc[ci]) for ci in range(nb)])
    else:
        out = jax.lax.scan(
            lambda c, args: (c, chunk(args[0], args[1])),
            jnp.zeros((), jnp.int32), (jnp.arange(nb), qc))[1]  # (nb,B,bq,H,D)
    out = out.transpose(1, 0, 2, 3, 4).reshape(b, nb * bq, hq, d)
    return out[:, :s]


def _maybe_seq_shard(x: jax.Array, cfg, seq_axis: int = 1) -> jax.Array:
    """attention_partitioning="seq": constrain the seq dim over "model"
    (batch keeps its dp axes).  No-op without an installed mesh."""
    if getattr(cfg, "attention_partitioning", "auto") != "seq":
        return x
    from jax.sharding import PartitionSpec as P

    from repro.parallel.ep import current_mesh
    from repro.sharding import dp_axes

    mesh = current_mesh()
    if mesh is None or "model" not in mesh.axis_names:
        return x
    if x.shape[seq_axis] % mesh.shape["model"] != 0:
        return x
    dp = dp_axes(mesh)
    dpsz = 1
    for a in dp:
        dpsz *= mesh.shape[a]
    entries = [None] * x.ndim
    if x.shape[0] % dpsz == 0 and dpsz > 1:
        entries[0] = dp
    entries[seq_axis] = "model"
    return jax.lax.with_sharding_constraint(x, P(*entries))


def attn_forward(
    p: Params,
    x: jax.Array,
    positions: jax.Array,
    cfg,
    window: int = 0,
    kv_override: Optional[Tuple[jax.Array, jax.Array]] = None,
    causal: bool = True,
) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Training / prefill attention.  Returns (out, (k, v)) for cache fill.

    ``kv_override`` turns this into cross-attention (positions are ignored for
    rope on kv).  ``window`` > 0 limits attention to the last ``window`` keys.
    """
    hd = cfg.resolved_head_dim
    n_rep = cfg.n_heads // cfg.n_kv_heads
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    if kv_override is None:
        k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
        v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    else:
        kv_src_k, kv_src_v = kv_override
        k = kv_src_k if kv_src_k.ndim == 4 else jnp.einsum("bsd,dhk->bshk", kv_src_k, p["wk"])
        v = kv_src_v if kv_src_v.ndim == 4 else jnp.einsum("bsd,dhk->bshk", kv_src_v, p["wv"])

    impl = getattr(cfg, "attention_impl", "xla")
    if impl in ("pallas", "pallas_interpret") and kv_override is None and causal and window == 0:
        from repro.kernels import ops as kops

        # "pallas" = auto (compat picks Mosaic on TPU / interpret on CPU);
        # "pallas_interpret" pins interpret mode for bit-exact test sweeps
        out = kops.flash_attention(
            q, k, v, causal=True,
            interpret=True if impl == "pallas_interpret" else None,
        )
    elif impl in ("blockwise", "blockwise_u") and kv_override is None and causal:
        q = _maybe_seq_shard(q, cfg)
        out = _blockwise_attention(q, k, v, n_rep, hd, window,
                                   getattr(cfg, "attention_block_q", 512),
                                   unroll=(impl == "blockwise_u"))
    else:
        if kv_override is None and causal:
            q = _maybe_seq_shard(q, cfg)
        scores = _gqa_scores(q, k, n_rep) / jnp.sqrt(hd).astype(jnp.float32)
        sq, sk = scores.shape[-2], scores.shape[-1]
        if kv_override is None and causal:
            iq = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
            ik = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
            mask = ik <= iq
            if window > 0:
                mask &= ik > iq - window
        else:
            mask = jnp.ones((sq, sk), dtype=bool)
        w = _masked_softmax(scores, mask, x.dtype)
        out = _gqa_out(w, v)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"]), (k, v)


def attn_decode(
    p: Params,
    x: jax.Array,
    cache_k: jax.Array,
    cache_v: jax.Array,
    pos: jax.Array,
    cfg,
    write_pos: Optional[jax.Array] = None,
    cross: bool = False,
) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Single-token decode.  x: (B,1,d); cache_{k,v}: (B,M,Hkv,D).

    ``pos`` (B,) is the ABSOLUTE position of the new token (drives RoPE and the
    valid-length mask).  ``write_pos`` (B,) is the cache slot to write —
    defaults to ``pos``; pass ``pos % M`` for circular sliding-window buffers.
    For ``cross=True`` the cache is the fixed encoder KV; nothing is written
    and every slot is attended.
    """
    hd = cfg.resolved_head_dim
    n_rep = cfg.n_heads // cfg.n_kv_heads
    b = x.shape[0]
    M = cache_k.shape[1]
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    if not cross:
        if write_pos is None:
            write_pos = pos
        k_new = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
        v_new = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
        q = apply_rope(q, pos[:, None], cfg.rope_theta)
        k_new = apply_rope(k_new, pos[:, None], cfg.rope_theta)
        # write the new kv at slot `write_pos` (per-batch dynamic index)
        oh = jax.nn.one_hot(write_pos, M, dtype=cache_k.dtype)  # (B, M)
        cache_k = cache_k * (1 - oh)[:, :, None, None] + oh[:, :, None, None] * k_new
        cache_v = cache_v * (1 - oh)[:, :, None, None] + oh[:, :, None, None] * v_new
    impl = getattr(cfg, "attention_impl", "xla")
    if impl in ("pallas", "pallas_interpret") and not cross:
        from repro.kernels import ops as kops

        valid = jnp.minimum(pos + 1, M)
        out = kops.decode_attention(
            q, cache_k, cache_v, valid,
            interpret=True if impl == "pallas_interpret" else None)
    else:
        scores = _gqa_scores(q, cache_k, n_rep) / jnp.sqrt(hd).astype(jnp.float32)
        ik = jax.lax.broadcasted_iota(jnp.int32, (b, 1, M), 2)
        if cross:
            mask = jnp.ones((b, 1, M), dtype=bool)
        else:
            # number of valid slots after the write: min(pos+1, M)
            valid = jnp.minimum(pos + 1, M)[:, None, None]
            mask = ik < valid
        w = _masked_softmax(scores, mask[:, None, None], x.dtype)  # (B,Hkv,G,1,M)
        out = _gqa_out(w, cache_v)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"]), (cache_k, cache_v)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def mlp_defs(cfg, d_ff: Optional[int] = None) -> Params:
    d, dff = cfg.d_model, d_ff or cfg.d_ff
    dt = adtype(cfg)
    if cfg.activation in ("swiglu", "geglu"):
        return {
            "w1": ParamDef((d, dff), ("embed", "mlp"), dtype=dt),
            "w3": ParamDef((d, dff), ("embed", "mlp"), dtype=dt),
            "w2": ParamDef((dff, d), ("mlp", "embed"), dtype=dt),
        }
    return {
        "w1": ParamDef((d, dff), ("embed", "mlp"), dtype=dt),
        "w2": ParamDef((dff, d), ("mlp", "embed"), dtype=dt),
    }


def apply_mlp(p: Params, x: jax.Array, activation: str) -> jax.Array:
    h = x @ p["w1"]
    if activation == "swiglu":
        h = jax.nn.silu(h) * (x @ p["w3"])
    elif activation == "geglu":
        h = jax.nn.gelu(h) * (x @ p["w3"])
    elif activation == "relu2":
        h = jnp.square(jax.nn.relu(h))
    elif activation == "gelu":
        h = jax.nn.gelu(h)
    else:
        raise ValueError(activation)
    return h @ p["w2"]


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------


def embed_defs(cfg) -> Params:
    dt = adtype(cfg)
    out = {
        "embedding": ParamDef(
            (cfg.vocab, cfg.d_model), ("vocab", "embed"), init="embed", scale=1.0, dtype=dt
        )
    }
    if not cfg.tie_embeddings:
        out["lm_head"] = ParamDef((cfg.d_model, cfg.vocab), ("embed", "vocab"), dtype=dt)
    return out


def embed_tokens(p: Params, tokens: jax.Array, cfg) -> jax.Array:
    x = p["embedding"][tokens]  # gather
    if cfg.embed_scale:
        x = x * jnp.sqrt(cfg.d_model).astype(x.dtype)
    return x


def unembed(p: Params, x: jax.Array, cfg) -> jax.Array:
    if cfg.tie_embeddings:
        return jnp.einsum("bsd,vd->bsv", x, p["embedding"])
    return jnp.einsum("bsd,dv->bsv", x, p["lm_head"])


def posembed_defs(cfg, max_len: int) -> Params:
    return {
        "pos": ParamDef((max_len, cfg.d_model), (None, "embed"), init="embed", scale=0.02,
                        dtype=adtype(cfg))
    }
