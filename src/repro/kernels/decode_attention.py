"""Flash-decode Pallas TPU kernel: one query token vs a long KV cache.

The decode bottleneck is pure HBM bandwidth (read M keys+values per head per
token).  The kernel streams (block_m x D) cache tiles through VMEM with the
same online-softmax scratch trick as flash attention; all G query heads of a
kv group share each streamed tile (GQA's arithmetic-intensity win, expressed
as a (G x block_m) score tile that keeps the MXU busy instead of a
vector-only dot).

Grid: (B, Hkv, nm), nm innermost/sequential.  The (B,) valid lengths are a
scalar-prefetch operand: the whole array sits in SMEM and each grid step
reads its batch row's length from it.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.compat import mosaic_params, resolve_interpret

NEG_INF = -1e30
LANES = 128


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                   *, scale: float, block_m: int, n_m: int):
    mi = pl.program_id(2)

    @pl.when(mi == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)           # (G, D)
    k = k_ref[0, 0].astype(jnp.float32)           # (bm, D)
    v = v_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale  # (G,bm)

    valid = len_ref[pl.program_id(0)]
    cols = mi * block_m + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(cols < valid, s, NEG_INF)

    m_prev = m_scr[:, :1]
    l_prev = l_scr[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
    acc = acc_scr[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)
    acc_scr[...] = acc

    @pl.when(mi == n_m - 1)
    def _finish():
        denom = jnp.where(l_scr[:, :1] == 0.0, 1.0, l_scr[:, :1])
        o_ref[0, 0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def decode_attention_bhd(q: jax.Array, k: jax.Array, v: jax.Array,
                         lengths: jax.Array, *, block_m: int = 512,
                         interpret: Optional[bool] = None) -> jax.Array:
    """q: (B,Hq,D); k,v: (B,Hkv,M,D); lengths: (B,) int32 -> (B,Hq,D).

    M must be a multiple of block_m (ops.py pads; padding is masked by
    ``lengths``).  ``interpret=None`` auto-selects per backend."""
    interpret = resolve_interpret(interpret)
    b, hq, d = q.shape
    hkv, m = k.shape[1], k.shape[2]
    group = hq // hkv
    block_m = min(block_m, m)
    if m % block_m:
        raise ValueError(f"cache len {m} % block_m {block_m}")
    n_m = m // block_m
    qg = q.reshape(b, hkv, group, d)

    kernel = functools.partial(_decode_kernel, scale=1.0 / (d ** 0.5),
                               block_m=block_m, n_m=n_m)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, hkv, n_m),
            in_specs=[
                pl.BlockSpec((1, 1, group, d),
                             lambda b_, h, mi, lens: (b_, h, 0, 0)),
                pl.BlockSpec((1, 1, block_m, d),
                             lambda b_, h, mi, lens: (b_, h, mi, 0)),
                pl.BlockSpec((1, 1, block_m, d),
                             lambda b_, h, mi, lens: (b_, h, mi, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, group, d),
                                   lambda b_, h, mi, lens: (b_, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((group, LANES), jnp.float32),
                pltpu.VMEM((group, LANES), jnp.float32),
                pltpu.VMEM((group, d), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((b, hkv, group, d), q.dtype),
        interpret=interpret,
        **mosaic_params(dimension_semantics=("parallel", "parallel",
                                             "arbitrary")),
    )(lengths, qg, k, v)
    return out.reshape(b, hq, d)
