"""Jit-able wrappers around the Pallas kernels (padding + layout glue).

Layout contract with the model code (repro.models.layers): activations are
(B, S, H, D) / caches are (B, M, Hkv, D); the kernels want head-major
(B, H, S, D).  Wrappers transpose, pad sequences to block multiples, call
the kernel, and slice back.

``interpret=None`` (the default) auto-selects through repro.compat: Mosaic
lowering on an accelerator, Python interpret mode on the CPU backend.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.compat import resolve_interpret
from repro.kernels import ssm_scan as _ssm
from repro.kernels.decode_attention import decode_attention_bhd
from repro.kernels.flash_attention import flash_attention_bhsd


def _pad_to(x: jax.Array, axis: int, multiple: int) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, block_q: int = 128,
                    block_k: int = 128,
                    interpret: Optional[bool] = None) -> jax.Array:
    """q: (B,S,Hq,D); k,v: (B,S,Hkv,D) -> (B,S,Hq,D).  Causal only (key
    padding is masked by causality)."""
    # resolve interpret=None OUTSIDE jit so the cache is keyed on the
    # concrete mode and env/backend changes can't hit a stale executable
    return _flash_attention(q, k, v, causal=causal, block_q=block_q,
                            block_k=block_k,
                            interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def _flash_attention(q, k, v, *, causal, block_q, block_k, interpret):
    if not causal:
        raise NotImplementedError("pallas path is causal-only; xla handles "
                                  "bidirectional encoders")
    s = q.shape[1]
    qt = _pad_to(jnp.swapaxes(q, 1, 2), 2, block_q)
    kt = _pad_to(jnp.swapaxes(k, 1, 2), 2, block_k)
    vt = _pad_to(jnp.swapaxes(v, 1, 2), 2, block_k)
    out = flash_attention_bhsd(qt, kt, vt, causal=True, block_q=block_q,
                               block_k=block_k, interpret=interpret)
    return jnp.swapaxes(out[:, :, :s], 1, 2)


def decode_attention(q: jax.Array, cache_k: jax.Array, cache_v: jax.Array,
                     lengths: jax.Array, *, block_m: int = 512,
                     interpret: Optional[bool] = None) -> jax.Array:
    """q: (B,1,Hq,D); cache_{k,v}: (B,M,Hkv,D); lengths (B,) -> (B,1,Hq,D).
    Cache padding beyond ``lengths`` is masked inside the kernel."""
    return _decode_attention(q, cache_k, cache_v, lengths, block_m=block_m,
                             interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("block_m", "interpret"))
def _decode_attention(q, cache_k, cache_v, lengths, *, block_m, interpret):
    qb = q[:, 0]  # (B,Hq,D)
    kt = _pad_to(jnp.swapaxes(cache_k, 1, 2), 2, block_m)
    vt = _pad_to(jnp.swapaxes(cache_v, 1, 2), 2, block_m)
    out = decode_attention_bhd(qb, kt, vt, lengths.astype(jnp.int32),
                               block_m=block_m, interpret=interpret)
    return out[:, None]


def ssm_scan(dA: jax.Array, dBx: jax.Array, C: jax.Array, *, chunk: int = 16,
             interpret: Optional[bool] = None):
    """Chunked linear recurrence + output contraction (see ssm_scan.py).
    Pads S to a chunk multiple; padded steps have dA=0, dBx=0 so h_last is
    exact... padded dA must be 1 to keep h; handled here."""
    return _ssm_scan(dA, dBx, C, chunk=chunk,
                     interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _ssm_scan(dA, dBx, C, *, chunk, interpret):
    s = dA.shape[1]
    pad = (-s) % chunk
    if pad:
        # identity steps: h_t = 1*h_{t-1} + 0 ; C=0 so y_pad = garbage-free
        dA = jnp.pad(dA, ((0, 0), (0, pad), (0, 0), (0, 0)),
                     constant_values=1.0)
        dBx = jnp.pad(dBx, ((0, 0), (0, pad), (0, 0), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
    y, h_last = _ssm.ssm_scan_chunked(dA, dBx, C, chunk=chunk,
                                      interpret=interpret)
    return y[:, :s], h_last


def ssm_scan_fused(delta: jax.Array, B: jax.Array, C: jax.Array,
                   x: jax.Array, A: jax.Array, *, chunk: int = 16,
                   interpret: Optional[bool] = None):
    """Fused-discretization selective scan (see ssm_scan.py): dA/dBx never
    touch HBM.  Pads S to a chunk multiple (identity steps)."""
    return _ssm_scan_fused(delta, B, C, x, A, chunk=chunk,
                           interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _ssm_scan_fused(delta, B, C, x, A, *, chunk, interpret):
    s = delta.shape[1]
    pad = (-s) % chunk
    if pad:
        # delta=0 => dA=1 (identity), dBx=0: state is preserved exactly
        delta = jnp.pad(delta, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
    y, h_last = _ssm.ssm_scan_fused(delta, B, C, x, A, chunk=chunk,
                                    interpret=interpret)
    return y[:, :s], h_last
