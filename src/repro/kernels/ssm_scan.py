"""Chunked selective-scan Pallas TPU kernel (hymba's SSM hot-spot).

The recurrence h_t = dA_t * h_{t-1} + dBx_t is memory-bound: the XLA
associative-scan materializes all (B,S,di,N) intermediates in HBM
(O(S log S) traffic).  The kernel streams (chunk, BLOCK_D, N) tiles through
VMEM, carries h in scratch across the sequential chunk grid dim, and fuses
the y_t = <h_t, C_t> contraction so h never round-trips to HBM — one read of
dA/dBx/C and one write of y total.

Grid: (B, di / BLOCK_D, n_chunks), chunks innermost/sequential.  The di
channels are independent, so tiling them bounds VMEM and gives the
parallel dims more work.

Mosaic layout: the steps of a chunk are unrolled with static indices (the
TPU lowering has no dynamic_slice on values).  Per-step column vectors
(y_t, and delta_t / x_t in the fused variant) live on the LANE axis of
channel-major (di, chunk) tiles, so every broadcast is along a native axis;
the wrappers transpose those operands to and from (B, S, di).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.compat import mosaic_params, resolve_interpret

# di channels per grid step: (chunk, BLOCK_D, N) f32 tiles of dA/dBx, with N
# padded to 128 lanes, stay a few MB of VMEM (the whole di would not fit)
BLOCK_D = 128


def _ssm_kernel(dA_ref, dBx_ref, C_ref, yT_ref, h_last_ref, h_scr, *,
                chunk: int, n_chunks: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    h = h_scr[...]                                       # (bd, N)
    for t in range(chunk):
        h = (dA_ref[0, t].astype(jnp.float32) * h
             + dBx_ref[0, t].astype(jnp.float32))
        c_t = C_ref[0, pl.ds(t, 1), :].astype(jnp.float32)   # (1, N)
        yT_ref[0, 0, :, pl.ds(t, 1)] = jnp.sum(h * c_t, axis=1, keepdims=True)
    h_scr[...] = h

    @pl.when(ci == n_chunks - 1)
    def _finish():
        h_last_ref[0] = h


def _ssm_fused_kernel(deltaT_ref, b_ref, c_ref, xT_ref, a_ref, yT_ref,
                      h_last_ref, h_scr, *, chunk: int, n_chunks: int):
    """Fused-discretization variant: dA/dBx are built IN VMEM from
    (delta, B, x, A) — HBM reads drop from O(S·di·N) to O(S·(di+N)),
    ~(di·N)/(di+N) x less traffic (e.g. 32x for di=3200, N=16).
    The math must stay in lockstep with ref.ssm_discretize."""
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    A = a_ref[...].astype(jnp.float32)                   # (bd, N)
    h = h_scr[...]
    for t in range(chunk):
        d_t = deltaT_ref[0, 0, :, pl.ds(t, 1)].astype(jnp.float32)  # (bd, 1)
        x_t = xT_ref[0, 0, :, pl.ds(t, 1)].astype(jnp.float32)      # (bd, 1)
        b_t = b_ref[0, pl.ds(t, 1), :].astype(jnp.float32)          # (1, N)
        c_t = c_ref[0, pl.ds(t, 1), :].astype(jnp.float32)
        h = jnp.exp(d_t * A) * h + (d_t * x_t) * b_t
        yT_ref[0, 0, :, pl.ds(t, 1)] = jnp.sum(h * c_t, axis=1, keepdims=True)
    h_scr[...] = h

    @pl.when(ci == n_chunks - 1)
    def _finish():
        h_last_ref[0] = h


def _tiling(s: int, di: int, chunk: int):
    chunk = min(chunk, s)
    if s % chunk:
        raise ValueError(f"seq {s} % chunk {chunk}")
    bd = BLOCK_D if di % BLOCK_D == 0 else di
    return chunk, s // chunk, bd, di // bd


def _channel_major(a: jax.Array, n_chunks: int, chunk: int) -> jax.Array:
    """(B, S, di) -> (B, n_chunks, di, chunk)."""
    b, _, di = a.shape
    return jnp.swapaxes(a.reshape(b, n_chunks, chunk, di), 2, 3)


def _seq_major(aT: jax.Array) -> jax.Array:
    """(B, n_chunks, di, chunk) -> (B, S, di)."""
    b, nc, di, chunk = aT.shape
    return jnp.swapaxes(aT, 2, 3).reshape(b, nc * chunk, di)


def _out_specs(bd: int, chunk: int, n: int):
    return [
        pl.BlockSpec((1, 1, bd, chunk), lambda b_, di_, ci: (b_, ci, di_, 0)),
        pl.BlockSpec((1, bd, n), lambda b_, di_, ci: (b_, di_, 0)),
    ]


def _out_shape(b: int, n_chunks: int, di: int, chunk: int, n: int):
    return [
        jax.ShapeDtypeStruct((b, n_chunks, di, chunk), jnp.float32),
        jax.ShapeDtypeStruct((b, di, n), jnp.float32),
    ]


def ssm_scan_fused(delta: jax.Array, B: jax.Array, C: jax.Array,
                   x: jax.Array, A: jax.Array, *, chunk: int = 16,
                   interpret: Optional[bool] = None):
    """delta,x: (B,S,di); B,C: (B,S,N); A: (di,N).  S % chunk == 0.
    Returns (y (B,S,di) f32, h_last (B,di,N) f32)."""
    interpret = resolve_interpret(interpret)
    b, s, di = delta.shape
    n = B.shape[-1]
    chunk, n_chunks, bd, n_d = _tiling(s, di, chunk)
    kernel = functools.partial(_ssm_fused_kernel, chunk=chunk,
                               n_chunks=n_chunks)
    col = pl.BlockSpec((1, 1, bd, chunk), lambda b_, di_, ci: (b_, ci, di_, 0))
    row = pl.BlockSpec((1, chunk, n), lambda b_, di_, ci: (b_, ci, 0))
    yT, h_last = pl.pallas_call(
        kernel,
        grid=(b, n_d, n_chunks),
        in_specs=[col, row, row, col,
                  pl.BlockSpec((bd, n), lambda b_, di_, ci: (di_, 0))],
        out_specs=_out_specs(bd, chunk, n),
        out_shape=_out_shape(b, n_chunks, di, chunk, n),
        scratch_shapes=[pltpu.VMEM((bd, n), jnp.float32)],
        interpret=interpret,
        **mosaic_params(dimension_semantics=("parallel", "parallel",
                                             "arbitrary")),
    )(_channel_major(delta, n_chunks, chunk), B, C,
      _channel_major(x, n_chunks, chunk), A)
    return _seq_major(yT), h_last


def ssm_scan_chunked(dA: jax.Array, dBx: jax.Array, C: jax.Array, *,
                     chunk: int = 16, interpret: Optional[bool] = None):
    """dA, dBx: (B,S,di,N); C: (B,S,N).  S must be a multiple of ``chunk``.
    Returns (y (B,S,di) f32, h_last (B,di,N) f32)."""
    interpret = resolve_interpret(interpret)
    b, s, di, n = dA.shape
    chunk, n_chunks, bd, n_d = _tiling(s, di, chunk)
    kernel = functools.partial(_ssm_kernel, chunk=chunk, n_chunks=n_chunks)
    tile = pl.BlockSpec((1, chunk, bd, n), lambda b_, di_, ci: (b_, ci, di_, 0))
    yT, h_last = pl.pallas_call(
        kernel,
        grid=(b, n_d, n_chunks),
        in_specs=[tile, tile,
                  pl.BlockSpec((1, chunk, n), lambda b_, di_, ci: (b_, ci, 0))],
        out_specs=_out_specs(bd, chunk, n),
        out_shape=_out_shape(b, n_chunks, di, chunk, n),
        scratch_shapes=[pltpu.VMEM((bd, n), jnp.float32)],
        interpret=interpret,
        **mosaic_params(dimension_semantics=("parallel", "parallel",
                                             "arbitrary")),
    )(dA, dBx, C)
    return _seq_major(yT), h_last
