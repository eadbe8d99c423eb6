"""xLSTM blocks: mLSTM (matrix memory, parallel train form + O(1) decode) and
sLSTM (scalar memory, sequential recurrence with exponential gating).

Follows arXiv:2405.04517.  The mLSTM training form is the stabilized
quadratic formulation; decode carries (C, n, m).  sLSTM blocks are strictly
sequential (lax.scan over time) with block-diagonal recurrent weights per head
and a small post-FFN.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models.params import ParamDef
from repro.models.layers import adtype, apply_norm, norm_defs

Params = Dict[str, Any]


def _logsigmoid(x):
    return -jax.nn.softplus(-x)


# ---------------------------------------------------------------------------
# mLSTM block
# ---------------------------------------------------------------------------


def mlstm_defs(cfg) -> Params:
    d = cfg.d_model
    dp = int(cfg.xlstm.proj_factor * d)
    h = cfg.n_heads
    dh = dp // h
    dt = adtype(cfg)
    return {
        "norm": norm_defs(cfg),
        "w_up": ParamDef((d, dp), ("embed", "inner"), dtype=dt),
        "w_gate": ParamDef((d, dp), ("embed", "inner"), dtype=dt),
        "conv_w": ParamDef((4, dp), (None, "inner"), init="scaled", scale=0.5, dtype=dt),
        "conv_b": ParamDef((dp,), ("inner",), init="zeros", dtype=dt),
        "w_q": ParamDef((dp, h, dh), ("inner", "heads", "head_dim"), dtype=dt),
        "w_k": ParamDef((dp, h, dh), ("inner", "heads", "head_dim"), dtype=dt),
        "w_v": ParamDef((dp, h, dh), ("inner", "heads", "head_dim"), dtype=dt),
        "w_i": ParamDef((d, h), ("embed", "heads"), dtype=jnp.float32),
        "b_i": ParamDef((h,), ("heads",), init="zeros", dtype=jnp.float32),
        "w_f": ParamDef((d, h), ("embed", "heads"), dtype=jnp.float32),
        "b_f": ParamDef((h,), ("heads",), init="ones", dtype=jnp.float32),
        "w_down": ParamDef((dp, d), ("inner", "embed"), dtype=dt),
    }


def _mlstm_qkvgates(p: Params, x: jax.Array, cfg, conv_state=None):
    xn = apply_norm(p["norm"], x, cfg.norm)
    u = xn @ p["w_up"]
    z = xn @ p["w_gate"]
    from repro.models.ssm import _causal_conv

    c, conv_state = _causal_conv(u, p["conv_w"], p["conv_b"], conv_state)
    c = jax.nn.silu(c)
    q = jnp.einsum("bsd,dhk->bshk", c, p["w_q"])
    k = jnp.einsum("bsd,dhk->bshk", c, p["w_k"]) / jnp.sqrt(q.shape[-1]).astype(c.dtype)
    v = jnp.einsum("bsd,dhk->bshk", u, p["w_v"])
    ig = (xn.astype(jnp.float32) @ p["w_i"] + p["b_i"])  # (B,S,H) log-space input gate
    fg = (xn.astype(jnp.float32) @ p["w_f"] + p["b_f"])
    return q, k, v, ig, fg, z, conv_state


def mlstm_forward(p: Params, x: jax.Array, cfg) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Parallel (training/prefill) form.  x: (B,S,d) -> (y, final decode state)."""
    q, k, v, ig, fg, z, conv_state = _mlstm_qkvgates(p, x, cfg)
    b, s, h, dh = q.shape
    logf = _logsigmoid(fg)  # (B,S,H)
    fcum = jnp.cumsum(logf, axis=1)
    # log-decay matrix: D[i,j] = fcum_i - fcum_j + ig_j  (j <= i)
    dmat = fcum[:, :, None, :] - fcum[:, None, :, :] + ig[:, None, :, :]  # (B,Si,Sj,H)
    ii = jax.lax.broadcasted_iota(jnp.int32, (s, s), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (s, s), 1)
    causal = (jj <= ii)[None, :, :, None]
    dmat = jnp.where(causal, dmat, -jnp.inf)
    m = jnp.max(dmat, axis=2, keepdims=True)  # (B,S,1,H)
    m = jnp.maximum(m, -1e30)  # guard all -inf rows
    dprime = jnp.exp(dmat - m)  # (B,Si,Sj,H)
    scores = jnp.einsum("bihk,bjhk->bijh", q.astype(jnp.float32), k.astype(jnp.float32))
    w = scores * dprime
    norm = jnp.maximum(jnp.abs(w.sum(axis=2)), jnp.exp(-m[:, :, 0]))  # (B,S,H)
    y = jnp.einsum("bijh,bjhk->bihk", w, v.astype(jnp.float32)) / norm[..., None]
    y = (y.astype(x.dtype) * jax.nn.silu(z).reshape(b, s, h, dh)).reshape(b, s, h * dh)
    # final recurrent state for decode handoff
    state = _mlstm_state_from_seq(q, k, v, ig, fg, conv_state)
    return y @ p["w_down"], state


def _mlstm_state_from_seq(q, k, v, ig, fg, conv_state) -> Dict[str, jax.Array]:
    """Fold the whole sequence into (C, n, m) so decode can continue."""
    b, s, h, dh = k.shape
    logf = _logsigmoid(fg)
    fcum = jnp.cumsum(logf, axis=1)
    total = fcum[:, -1:, :]  # (B,1,H)
    # weight of step j in final state: exp(total - fcum_j + ig_j)
    logw = (total - fcum + ig)  # (B,S,H)
    m = jnp.max(logw, axis=1)  # (B,H)
    wgt = jnp.exp(logw - m[:, None, :])  # (B,S,H)
    kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)
    C = jnp.einsum("bsh,bshd,bshe->bhde", wgt, kf, vf)
    n = jnp.einsum("bsh,bshd->bhd", wgt, kf)
    return {"C": C, "n": n, "m": m, "conv": conv_state}


def mlstm_decode(p: Params, x: jax.Array, state: Dict[str, jax.Array], cfg
                 ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """O(1) recurrent step.  x: (B,1,d)."""
    q, k, v, ig, fg, z, conv_state = _mlstm_qkvgates(p, x, cfg, state["conv"])
    b, _, h, dh = q.shape
    qf, kf, vf = (t[:, 0].astype(jnp.float32) for t in (q, k, v))
    ig1, fg1 = ig[:, 0], fg[:, 0]  # (B,H)
    logf = _logsigmoid(fg1)
    m_new = jnp.maximum(logf + state["m"], ig1)
    fprime = jnp.exp(logf + state["m"] - m_new)[..., None]
    iprime = jnp.exp(ig1 - m_new)[..., None]
    C = state["C"] * fprime[..., None] + iprime[..., None] * jnp.einsum("bhd,bhe->bhde", kf, vf)
    n = state["n"] * fprime + iprime * kf
    num = jnp.einsum("bhde,bhd->bhe", C, qf)
    den = jnp.maximum(jnp.abs(jnp.einsum("bhd,bhd->bh", n, qf)), jnp.exp(-m_new))
    y = (num / den[..., None]).astype(x.dtype)  # (B,H,Dh)
    y = (y.reshape(b, 1, h * dh) * jax.nn.silu(z))
    return y @ p["w_down"], {"C": C, "n": n, "m": m_new, "conv": conv_state}


def init_mlstm_state(cfg, batch: int) -> Dict[str, jax.Array]:
    d = cfg.d_model
    dp = int(cfg.xlstm.proj_factor * d)
    h = cfg.n_heads
    dh = dp // h
    return {
        "C": jnp.zeros((batch, h, dh, dh), jnp.float32),
        "n": jnp.zeros((batch, h, dh), jnp.float32),
        "m": jnp.full((batch, h), -1e30, jnp.float32),
        "conv": jnp.zeros((batch, 3, dp), adtype(cfg)),
    }


# ---------------------------------------------------------------------------
# sLSTM block
# ---------------------------------------------------------------------------


def slstm_defs(cfg) -> Params:
    d, h = cfg.d_model, cfg.n_heads
    dh = d // h
    dt = adtype(cfg)
    dffn = int(2 * d)
    return {
        "norm": norm_defs(cfg),
        # gate input projections: z, i, f, o
        "w_x": ParamDef((d, 4, h, dh), ("embed", None, "heads", "head_dim"), dtype=jnp.float32),
        # block-diagonal recurrent weights per head
        "r_h": ParamDef((4, h, dh, dh), (None, "heads", "head_dim", None),
                        init="normal", dtype=jnp.float32),
        "b": ParamDef((4, h, dh), (None, "heads", "head_dim"), init="zeros", dtype=jnp.float32),
        "ffn_norm": norm_defs(cfg),
        "ffn_w1": ParamDef((d, dffn), ("embed", "mlp"), dtype=dt),
        "ffn_w2": ParamDef((dffn, d), ("mlp", "embed"), dtype=dt),
    }


def _slstm_cell(p: Params, xt: jax.Array, state: Dict[str, jax.Array]):
    """xt: (B,4,H,Dh) pre-projected gate inputs."""
    h_prev = state["h"]  # (B,H,Dh)
    rec = jnp.einsum("bhd,ghde->bghe", h_prev, p["r_h"])  # (B,4,H,Dh)
    g = xt + rec + p["b"]
    zt = jnp.tanh(g[:, 0])
    it = g[:, 1]  # log-space
    ft = _logsigmoid(g[:, 2])
    ot = jax.nn.sigmoid(g[:, 3])
    m_new = jnp.maximum(ft + state["m"], it)
    iprime = jnp.exp(it - m_new)
    fprime = jnp.exp(ft + state["m"] - m_new)
    c = fprime * state["c"] + iprime * zt
    n = fprime * state["n"] + iprime
    h = ot * c / jnp.maximum(n, 1e-6)
    return {"c": c, "n": n, "h": h, "m": m_new}


def slstm_forward(p: Params, x: jax.Array, cfg, state: Dict[str, jax.Array] = None
                  ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Sequential over time.  x: (B,S,d)."""
    b, s, d = x.shape
    h, dh = cfg.n_heads, d // cfg.n_heads
    xn = apply_norm(p["norm"], x, cfg.norm)
    xg = jnp.einsum("bsd,dghe->bsghe", xn.astype(jnp.float32), p["w_x"])  # (B,S,4,H,Dh)
    if state is None:
        state = init_slstm_state(cfg, b)

    def step(st, xt):
        st = _slstm_cell(p, xt, st)
        return st, st["h"]

    state, hs = jax.lax.scan(step, state, jnp.swapaxes(xg, 0, 1))
    y = jnp.swapaxes(hs, 0, 1).reshape(b, s, d).astype(x.dtype)
    y = x + y  # residual around the cell
    yn = apply_norm(p["ffn_norm"], y, cfg.norm)
    y = y + (jax.nn.gelu(yn @ p["ffn_w1"]) @ p["ffn_w2"])
    return y, state


def slstm_decode(p: Params, x: jax.Array, state: Dict[str, jax.Array], cfg
                 ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    y, state = slstm_forward(p, x, cfg, state)
    return y, state


def init_slstm_state(cfg, batch: int) -> Dict[str, jax.Array]:
    h, dh = cfg.n_heads, cfg.d_model // cfg.n_heads
    # one buffer per leaf: the serving engine donates its cache, and a buffer
    # that appears twice in a donated argument cannot be donated
    def z():
        return jnp.zeros((batch, h, dh), jnp.float32)
    return {"c": z(), "n": z(), "h": z(),
            "m": jnp.full((batch, h, dh), -1e30, jnp.float32)}
