"""Model assembly for all assigned architecture families.

Families:
  dense / vlm   — pre-norm attention + MLP blocks (vlm prepends stub image embeds)
  moe           — attention + MoE FFN (aux load-balance loss accumulated)
  hybrid        — hymba: parallel attention & mamba mixers, then MLP
  ssm           — xlstm: interleaved mLSTM / sLSTM blocks (unrolled)
  encdec        — whisper: bidirectional encoder (stub frame embeds) + causal
                  decoder with cross-attention

Three entry points per model:
  forward_train(params, cfg, batch)            -> (loss, metrics)
  prefill(params, cfg, batch, max_len)         -> (logits_last, cache)
  decode_step(params, cfg, cache, tokens)      -> (logits, cache)

Homogeneous stacks iterate with lax.scan over stacked per-layer params
(compile-time O(1) in depth); heterogeneous stacks (xlstm) unroll.
"""
from __future__ import annotations

import dataclasses

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import moe as MOE
from repro.models import ssm as SSM
from repro.models import xlstm as XL
from repro.models.params import ParamDef, is_paramdef

Params = Dict[str, Any]


def stack_defs(defs: Any, n: int) -> Any:
    return jax.tree_util.tree_map(
        lambda d: dataclasses.replace(d, shape=(n,) + d.shape,
                                      axes=("layers",) + d.axes),
        defs,
        is_leaf=is_paramdef,
    )


# ---------------------------------------------------------------------------
# Per-family block definitions
# ---------------------------------------------------------------------------


def block_defs(cfg: ModelConfig) -> Params:
    d: Params = {"ln_attn": L.norm_defs(cfg), "attn": L.attention_defs(cfg)}
    if cfg.family in ("dense", "vlm"):
        d["ln_mlp"] = L.norm_defs(cfg)
        d["mlp"] = L.mlp_defs(cfg)
    elif cfg.family == "moe":
        d["ln_mlp"] = L.norm_defs(cfg)
        d["moe"] = MOE.moe_defs(cfg)
    elif cfg.family == "hybrid":
        d["ssm"] = SSM.ssm_defs(cfg)
        d["mix_w"] = ParamDef((2,), (None,), init="ones", dtype=jnp.float32)
        d["ln_mlp"] = L.norm_defs(cfg)
        d["mlp"] = L.mlp_defs(cfg)
    else:
        raise ValueError(cfg.family)
    return d


def enc_block_defs(cfg: ModelConfig) -> Params:
    return {
        "ln_attn": L.norm_defs(cfg),
        "attn": L.attention_defs(cfg),
        "ln_mlp": L.norm_defs(cfg),
        "mlp": L.mlp_defs(cfg),
    }


def dec_block_defs(cfg: ModelConfig) -> Params:
    return {
        "ln_attn": L.norm_defs(cfg),
        "attn": L.attention_defs(cfg),
        "ln_cross": L.norm_defs(cfg),
        "cross": L.cross_attention_defs(cfg),
        "ln_mlp": L.norm_defs(cfg),
        "mlp": L.mlp_defs(cfg),
    }


def xlstm_layer_kinds(cfg: ModelConfig):
    ev = cfg.xlstm.slstm_every
    return ["slstm" if (ev and (i + 1) % ev == 0) else "mlstm" for i in range(cfg.n_layers)]


def model_defs(cfg: ModelConfig, max_seq: int = 0) -> Params:
    """Full parameter tree.  ``max_seq`` sizes absolute position tables
    (rope models ignore it)."""
    defs: Params = {"embed": L.embed_defs(cfg), "ln_f": L.norm_defs(cfg)}
    if cfg.family == "ssm":
        blocks = []
        for kind in xlstm_layer_kinds(cfg):
            blocks.append(XL.mlstm_defs(cfg) if kind == "mlstm" else XL.slstm_defs(cfg))
        defs["blocks"] = blocks
    elif cfg.family == "encdec":
        if cfg.layer_impl == "scan":
            defs["enc_blocks"] = stack_defs(enc_block_defs(cfg), cfg.n_enc_layers)
            defs["blocks"] = stack_defs(dec_block_defs(cfg), cfg.n_layers)
        else:
            defs["enc_blocks"] = [enc_block_defs(cfg)
                                  for _ in range(cfg.n_enc_layers)]
            defs["blocks"] = [dec_block_defs(cfg) for _ in range(cfg.n_layers)]
        defs["enc_ln_f"] = L.norm_defs(cfg)
        defs["enc_pos"] = L.posembed_defs(cfg, cfg.enc_frames)
        defs["dec_pos"] = L.posembed_defs(cfg, max(max_seq, 8))
    else:
        if cfg.layer_impl == "scan":
            defs["blocks"] = stack_defs(block_defs(cfg), cfg.n_layers)
        else:
            defs["blocks"] = [block_defs(cfg) for _ in range(cfg.n_layers)]
    if cfg.family == "vlm":
        defs["img_proj"] = {
            "w": ParamDef((cfg.d_model, cfg.d_model), ("embed", "embed_out"), dtype=L.adtype(cfg))
        }
    return defs


# ---------------------------------------------------------------------------
# Block applications (train/prefill produce per-layer cache entries)
# ---------------------------------------------------------------------------


def _apply_block(p: Params, x, positions, cfg, window, want_kv: bool):
    """One decoder block.  Returns (x, (k, v, extra_state, aux))."""
    aux = jnp.zeros((), jnp.float32)
    xn = L.apply_norm(p["ln_attn"], x, cfg.norm)
    attn_out, (k, v) = L.attn_forward(p["attn"], xn, positions, cfg, window=window)
    extra = ()
    if cfg.family == "hybrid":
        ssm_out, ssm_state = SSM.ssm_forward(p["ssm"], xn, cfg)
        w = jax.nn.relu(p["mix_w"])  # learned non-negative mixing
        x = x + (w[0] * attn_out.astype(jnp.float32)
                 + w[1] * ssm_out.astype(jnp.float32)).astype(x.dtype)
        extra = (ssm_state["conv"], ssm_state["ssm"])
    else:
        x = x + attn_out
    xn2 = L.apply_norm(p["ln_mlp"], x, cfg.norm)
    if cfg.family == "moe":
        ffn_out, aux = MOE.apply_moe(p["moe"], xn2, cfg)
    else:
        ffn_out = L.apply_mlp(p["mlp"], xn2, cfg.activation)
    x = x + ffn_out
    if want_kv:
        return x, (k, v, extra, aux)
    return x, ((), (), extra if cfg.family == "hybrid" else (), aux)


def _run_stack(params, x, positions, cfg, window, want_kv, remat: bool):
    """Iterate decoder blocks; returns (x, stacked per-layer outs)."""
    body = functools.partial(_apply_block, positions=positions, cfg=cfg, window=window,
                             want_kv=want_kv)
    if remat:
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        )

    if cfg.layer_impl == "scan" and not isinstance(params, list):
        x, outs = jax.lax.scan(lambda c, lp: body(lp, c), x, params)
        return x, outs
    outs = []
    for lp in params:
        x, o = body(lp, x)
        outs.append(o)
    return x, outs


# ---------------------------------------------------------------------------
# Embedding frontends
# ---------------------------------------------------------------------------


def _embed_inputs(params, cfg, batch) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Returns (x, positions, loss_mask_prefix) handling the vlm stub frontend."""
    tokens = batch["tokens"]
    x = L.embed_tokens(params["embed"], tokens, cfg)
    if cfg.family == "vlm":
        img = batch["img_embeds"].astype(x.dtype) @ params["img_proj"]["w"]
        x = jnp.concatenate([img, x], axis=1)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    return x, positions, tokens


# ---------------------------------------------------------------------------
# Training forward + loss
# ---------------------------------------------------------------------------


def cross_entropy(logits: jax.Array, targets: jax.Array, mask: jax.Array) -> jax.Array:
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = (lse - gold) * mask
    return nll.sum() / jnp.maximum(mask.sum(), 1.0)


def forward_logits(params: Params, cfg: ModelConfig, batch: Dict[str, jax.Array],
                   window: int = 0, remat: bool = False
                   ) -> Tuple[jax.Array, jax.Array]:
    """Decoder-only forward over whole sequences: (logits at every text
    position, summed auxiliary loss).  No cache — the plain reference the
    cached prefill/decode path is checked against."""
    x, positions, _ = _embed_inputs(params, cfg, batch)
    if cfg.family == "ssm":
        x, aux_total = _run_xlstm(params, x, cfg)
    else:
        x, outs = _run_stack(params["blocks"], x, positions, cfg, window,
                             want_kv=False, remat=remat)
        auxs = outs[3] if not isinstance(outs, list) else jnp.stack([o[3] for o in outs])
        aux_total = jnp.sum(auxs)
    x = L.apply_norm(params["ln_f"], x, cfg.norm)
    if cfg.family == "vlm":  # strip image positions before unembedding
        x = x[:, -batch["tokens"].shape[1]:]
    return L.unembed(params["embed"], x, cfg), aux_total


def forward_train(params: Params, cfg: ModelConfig, batch: Dict[str, jax.Array],
                  window: int = 0, remat: bool = True) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    if cfg.family == "encdec":
        return _forward_train_encdec(params, cfg, batch, remat)
    logits, aux_total = forward_logits(params, cfg, batch, window, remat)
    loss = cross_entropy(logits, batch["targets"], batch["mask"])
    total = loss + 0.01 * aux_total
    return total, {"loss": loss, "aux": aux_total}


def _run_xlstm(params, x, cfg):
    kinds = xlstm_layer_kinds(cfg)
    for kind, p in zip(kinds, params["blocks"]):
        if kind == "mlstm":
            out, _ = XL.mlstm_forward(p, x, cfg)
            x = x + out
        else:
            x, _ = XL.slstm_forward(p, x, cfg)  # residuals internal
    return x, jnp.zeros((), jnp.float32)


def _run_blocks(body, x, blocks):
    """Iterate a (scanned|unrolled) homogeneous stack, discarding per-layer
    outputs."""
    if isinstance(blocks, list):
        for lp in blocks:
            x, _ = body(lp, x)
        return x
    x, _ = jax.lax.scan(lambda c, lp: body(lp, c), x, blocks)
    return x


def _forward_train_encdec(params, cfg, batch, remat):
    frames = batch["enc_frames"].astype(L.adtype(cfg))
    enc = frames + params["enc_pos"]["pos"][None, : frames.shape[1]]
    b = enc.shape[0]
    enc_pos = jnp.broadcast_to(jnp.arange(enc.shape[1], dtype=jnp.int32), (b, enc.shape[1]))

    def enc_block(p, x):
        xn = L.apply_norm(p["ln_attn"], x, cfg.norm)
        a, _ = L.attn_forward(p["attn"], xn, enc_pos, cfg, causal=False)
        x = x + a
        xn = L.apply_norm(p["ln_mlp"], x, cfg.norm)
        return x + L.apply_mlp(p["mlp"], xn, cfg.activation), ()

    enc = _run_blocks(enc_block, enc, params["enc_blocks"])
    enc = L.apply_norm(params["enc_ln_f"], enc, cfg.norm)

    tokens = batch["tokens"]
    x = L.embed_tokens(params["embed"], tokens, cfg)
    s = x.shape[1]
    x = x + params["dec_pos"]["pos"][None, :s]
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))

    def dec_block(p, x):
        xn = L.apply_norm(p["ln_attn"], x, cfg.norm)
        a, _ = L.attn_forward(p["attn"], xn, positions, cfg)
        x = x + a
        xn = L.apply_norm(p["ln_cross"], x, cfg.norm)
        c, _ = L.attn_forward(p["cross"], xn, positions, cfg, kv_override=(enc, enc))
        x = x + c
        xn = L.apply_norm(p["ln_mlp"], x, cfg.norm)
        return x + L.apply_mlp(p["mlp"], xn, cfg.activation), ()

    body = dec_block
    if remat:
        body = jax.checkpoint(
            dec_block, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        )
    x = _run_blocks(body, x, params["blocks"])
    x = L.apply_norm(params["ln_f"], x, cfg.norm)
    logits = L.unembed(params["embed"], x, cfg)
    loss = cross_entropy(logits, batch["targets"], batch["mask"])
    return loss, {"loss": loss, "aux": jnp.zeros((), jnp.float32)}
