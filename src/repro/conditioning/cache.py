"""Prompt-level embedding cache: content-hashed, LRU-bounded.

Prompt embeddings are deterministic per prompt and step-invariant across
the whole denoise trajectory — the static-reuse end of the survey's
static->dynamic spectrum.  PromptCache therefore pays the text encoder
exactly once per UNIQUE prompt; every re-submission (the common serving
case: popular prompts, CFG pairs, retries) is a host-side dict hit.  The
per-slot cross-attn K/V tables downstream (engine._build_text_tables)
extend the same invariance: K/V projections happen once at admission,
never per step.

Entries are keyed by a content hash of the PADDED token buffer, so a
string prompt and its explicit token-sequence spelling share one entry.
Hit/miss/eviction counts publish through repro.obs metrics
(`repro_conditioning_prompt_cache_*`).
"""
from __future__ import annotations

import hashlib
from collections import OrderedDict
from dataclasses import dataclass
from typing import Optional

import jax.numpy as jnp
import numpy as np

import jax

from repro.obs import compile_program
from repro.obs.profiling import capture_ir

from .encoder import (TextEncoderConfig, TokensLike, encode_tokens,
                      pooled_embedding, tokenize)

__all__ = ["PromptEmbedding", "PromptCache"]


@dataclass(frozen=True)
class PromptEmbedding:
    """One cached prompt: padded tokens + the two embedding views."""
    key: str                     # content hash of the padded token buffer
    tokens: np.ndarray           # (L,) int32
    mask: np.ndarray             # (L,) bool
    embed: np.ndarray            # (L, d) f32, zeroed at padding
    pooled: np.ndarray           # (d,) f32 masked mean (neg-prompt vector)


class PromptCache:
    """prompt -> PromptEmbedding with LRU bounds and obs metrics.

    Host-side by design: admission-time code (SlotScheduler refill), not
    tick-path code — the one device->host transfer per unique prompt is
    the price of keeping every tick program free of text-encoder FLOPs.
    `warmup()` AOT-compiles the encoder program so a prompt-bearing
    admission after `engine.warmup()` compiles nothing (the retrace
    sentinel's zero-recompile claim extends over text serving)."""

    def __init__(self, params, tc: TextEncoderConfig, capacity: int = 128,
                 metrics=None, name: str = "default"):
        if capacity < 1:
            raise ValueError(f"PromptCache capacity must be >= 1, "
                             f"got {capacity}")
        self.params = params
        self.tc = tc
        self.capacity = int(capacity)
        self.name = name
        self._entries: "OrderedDict[str, PromptEmbedding]" = OrderedDict()
        self._metrics = metrics
        self.hits = 0
        self.misses = 0
        self.evictions = 0

        def _encode(params, ids, mask):
            # the batch squeeze lives INSIDE the program: an eager [0] on
            # the result would compile tiny slice/squeeze programs at the
            # first in-session miss, tripping the retrace sentinel
            emb = encode_tokens(params, ids, mask, tc)
            return emb[0], pooled_embedding(emb, mask)[0]

        self._encode_src = _encode          # kept for IR re-capture
        self._encode = jax.jit(_encode)
        self._compiled = None               # warmup() swaps in the AOT exe

    # ------------------------------------------------------------------
    def __len__(self) -> int:
        return len(self._entries)

    def content_key(self, prompt: TokensLike) -> str:
        ids, mask = tokenize(prompt, self.tc)
        return self._hash(ids, mask)

    @staticmethod
    def _hash(ids: np.ndarray, mask: np.ndarray) -> str:
        return hashlib.sha1(ids.tobytes() + mask.tobytes()).hexdigest()

    def _count(self, what: str, amount: int = 1) -> None:
        if what in ("hits", "misses", "evictions"):
            setattr(self, what, getattr(self, what) + amount)
        if self._metrics is not None:
            self._metrics.counter(
                f"repro_conditioning_prompt_cache_{what}_total",
                "PromptCache LRU events").inc(amount, cache=self.name)
            self._metrics.gauge(
                "repro_conditioning_prompt_cache_size",
                "live PromptCache entries").set(len(self._entries),
                                                cache=self.name)

    # ------------------------------------------------------------------
    def get(self, prompt: TokensLike) -> PromptEmbedding:
        """Embedding table for `prompt` — encoder runs only on a miss."""
        ids, mask = tokenize(prompt, self.tc)
        key = self._hash(ids, mask)
        hit = self._entries.get(key)
        if hit is not None:
            self._entries.move_to_end(key)
            self._count("hits")
            return hit
        fn = self._compiled if self._compiled is not None else self._encode
        emb_dev, pool_dev = fn(self.params, jnp.asarray(ids[None]),
                               jnp.asarray(mask[None]))
        # repro-lint: disable-next-line=host-sync-in-hot-path -- admission-
        # time transfer, paid once per UNIQUE prompt (never per tick/step)
        emb = np.asarray(emb_dev, np.float32)
        pool = np.asarray(pool_dev, np.float32)
        entry = PromptEmbedding(key=key, tokens=ids, mask=mask,
                                embed=emb, pooled=pool)
        self._entries[key] = entry
        self._count("misses")
        while len(self._entries) > self.capacity:
            self._entries.popitem(last=False)
            self._count("evictions")
        return entry

    # ------------------------------------------------------------------
    def _example_args(self):
        L = self.tc.max_len
        return (self.params, jnp.zeros((1, L), jnp.int32),
                jnp.zeros((1, L), bool))

    def warmup(self, verify: bool = False):
        """AOT-compile the encoder program; returns its ProgramProfile
        (plus the ProgramIR under `verify=True`).  The compiled executable
        replaces the lazy jit so post-warmup misses never trigger a
        compile."""
        out = compile_program(self._encode, *self._example_args(),
                              key="text_encoder", want_ir=verify)
        self._compiled = out[0]
        return out[1:] if verify else out[1]

    def capture_ir(self):
        """Re-capture the encoder program's IR (engine._capture_program_ir
        hook — a Compiled executable no longer carries its jaxpr)."""
        return capture_ir(jax.jit(self._encode_src), *self._example_args(),
                          key="text_encoder")

    # ------------------------------------------------------------------
    @property
    def stats(self) -> dict:
        return {"hits": self.hits, "misses": self.misses,
                "evictions": self.evictions, "size": len(self._entries),
                "capacity": self.capacity,
                "hit_rate": self.hits / max(self.hits + self.misses, 1)}
