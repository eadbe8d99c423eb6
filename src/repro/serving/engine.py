"""Batched serving engine with continuous batching (slot refill).

A fixed pool of ``max_batch`` decode slots shares one batched KV cache.
Requests queue up; a free slot is filled by prefilling the request at batch=1
and scattering its cache into the slot (per-leaf dynamic_update on the batch
axis).  Decode ticks advance every active slot one token; finished slots are
refilled immediately — decode never drains the whole batch to admit work.

Prompt padding: attention-family caches are position-indexed, so prompts are
right-padded to ``prefill_len`` and masked via the cache's valid-length
(``pos``); the first generated token is produced by re-decoding the last
prompt token (idempotent KV write), which sidesteps the padded-last-position
logits problem.  Recurrent families (ssm/hybrid) fold pads into their state,
so the engine requires exact-length prompts for them.

The three device programs (prefill, cache insert, decode) are compiled ahead
of time when the engine is built, against the engine's fixed shapes; insert
and decode donate the batched cache, so a tick holds one copy of it.
``programs`` keeps the compiled executables for inspection (HLO text,
``memory_analysis()``) and ``compile_s`` the time it took.
"""
from __future__ import annotations

import dataclasses
import itertools
import time
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import decoding as DEC

Params = Dict[str, Any]


@dataclasses.dataclass
class Request:
    id: int
    prompt: List[int]
    max_new_tokens: int
    eos_id: Optional[int] = None
    generated: List[int] = dataclasses.field(default_factory=list)
    done: bool = False


def _batch_axis(keypath: str) -> int:
    """Batch axis per cache leaf (see decoding.py cache layouts)."""
    for marker in ("'k'", "'v'", "'conv'", "'ssm'", "'cross_k'", "'cross_v'"):
        if marker in keypath:
            return 1  # (L, B, ...)
    return 0  # pos (B,), xlstm block states (B, ...)


class ServingEngine:
    def __init__(self, cfg: ModelConfig, params: Params, *, max_batch: int = 4,
                 max_len: int = 128, prefill_len: int = 32):
        if cfg.family == "encdec":
            raise NotImplementedError("serving engine targets decoder LMs")
        self.cfg = cfg
        self.params = params
        self.max_batch = max_batch
        self.max_len = max_len
        self.prefill_len = prefill_len
        self._ids = itertools.count()
        self.pending: deque = deque()
        self.slots: List[Optional[Request]] = [None] * max_batch
        self.finished: Dict[int, Request] = {}
        self.stats = {"prefills": 0, "decode_ticks": 0, "tokens": 0}

        self.cache = DEC.init_cache(cfg, max_batch, max_len)
        self._cache_axes = [
            _batch_axis(jax.tree_util.keystr(p))
            for p, _ in jax.tree_util.tree_flatten_with_path(self.cache)[0]]

        def prefill(params, toks):
            return DEC.prefill(params, cfg, {"tokens": toks}, max_len=max_len)

        def decode(params, cache, toks):
            return DEC.decode_step(params, cfg, cache, toks)

        def insert(cache, cache1, slot):
            flat, tdef = jax.tree_util.tree_flatten(cache)
            flat1 = jax.tree_util.tree_leaves(cache1)
            out = []
            for leaf, leaf1, ax in zip(flat, flat1, self._cache_axes):
                idx = [0] * leaf.ndim
                idx[ax] = slot
                out.append(jax.lax.dynamic_update_slice(leaf, leaf1.astype(
                    leaf.dtype), tuple(idx)))
            return jax.tree_util.tree_unflatten(tdef, out)

        toks1 = jax.ShapeDtypeStruct((1, prefill_len), jnp.int32)
        cache1 = jax.eval_shape(prefill, params, toks1)[1]
        t0 = time.perf_counter()
        self.programs = {
            "prefill": jax.jit(prefill).lower(params, toks1).compile(),
            "insert": jax.jit(insert, donate_argnums=0).lower(
                self.cache, cache1,
                jax.ShapeDtypeStruct((), jnp.int32)).compile(),
            "decode": jax.jit(decode, donate_argnums=1).lower(
                params, self.cache,
                jax.ShapeDtypeStruct((max_batch, 1), jnp.int32)).compile(),
        }
        self.compile_s = time.perf_counter() - t0

    # -- public ------------------------------------------------------------

    def submit(self, prompt: List[int], max_new_tokens: int = 16,
               eos_id: Optional[int] = None) -> int:
        if self.cfg.family in ("ssm", "hybrid") and len(prompt) != self.prefill_len:
            raise ValueError(
                f"recurrent family {self.cfg.family!r} needs exact-length "
                f"prompts ({self.prefill_len}); got {len(prompt)}")
        if len(prompt) > self.prefill_len:
            raise ValueError(f"prompt longer than prefill_len={self.prefill_len}")
        rid = next(self._ids)
        self.pending.append(Request(rid, list(prompt), max_new_tokens, eos_id))
        return rid

    def run_until_idle(self, max_ticks: int = 10_000) -> Dict[int, List[int]]:
        for _ in range(max_ticks):
            if not self.step():
                break
        return {rid: r.generated for rid, r in self.finished.items()}

    def step(self) -> bool:
        """One engine tick: admit into free slots, then decode.  Returns
        False when fully idle."""
        admitted = False
        for i, slot in enumerate(self.slots):
            if slot is None and self.pending:
                self._admit(i, self.pending.popleft())
                admitted = True
        active = [r for r in self.slots if r is not None]
        if not active:
            return admitted
        self._decode_tick()
        return True

    # -- internals ------------------------------------------------------------

    def _admit(self, slot: int, req: Request) -> None:
        plen = len(req.prompt)
        toks = np.zeros((1, self.prefill_len), np.int32)
        toks[0, :plen] = req.prompt
        logits1, cache1 = self.programs["prefill"](self.params,
                                                   jnp.asarray(toks))
        if self.cfg.family in ("ssm", "hybrid"):
            # recurrent state is NOT idempotent: take the first token from
            # the prefill logits directly (prompts are exact-length here)
            first = int(np.asarray(jnp.argmax(logits1[:, -1, :], axis=-1))[0])
            req.generated.append(first)
            req._next_input = first  # type: ignore[attr-defined]
            self.stats["tokens"] += 1
            if (len(req.generated) >= req.max_new_tokens
                    or (req.eos_id is not None and first == req.eos_id)):
                req.done = True
                self.finished[req.id] = req
                self.stats["prefills"] += 1
                return
        else:
            # rewind one token: the first decode re-processes the last prompt
            # token (idempotent kv write), yielding the first new-token logits
            cache1["pos"] = jnp.full((1,), plen - 1, jnp.int32)
            req._next_input = req.prompt[-1]  # type: ignore[attr-defined]
        self.cache = self.programs["insert"](self.cache, cache1,
                                             np.int32(slot))
        self.slots[slot] = req
        self.stats["prefills"] += 1

    def _decode_tick(self) -> None:
        toks = np.zeros((self.max_batch, 1), np.int32)
        for i, req in enumerate(self.slots):
            if req is not None:
                toks[i, 0] = req._next_input  # type: ignore[attr-defined]
        logits, self.cache = self.programs["decode"](self.params, self.cache,
                                                     jnp.asarray(toks))
        nxt = np.asarray(jnp.argmax(logits[:, -1, :], axis=-1))
        self.stats["decode_ticks"] += 1
        for i, req in enumerate(self.slots):
            if req is None:
                continue
            tok = int(nxt[i])
            req.generated.append(tok)
            req._next_input = tok  # type: ignore[attr-defined]
            self.stats["tokens"] += 1
            pos = int(np.asarray(self.cache["pos"])[i])
            if (len(req.generated) >= req.max_new_tokens
                    or (req.eos_id is not None and tok == req.eos_id)
                    or pos >= self.max_len - 1):
                req.done = True
                self.finished[req.id] = req
                self.slots[i] = None
