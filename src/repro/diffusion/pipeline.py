"""CachedDenoiser — binds repro.core cache policies to a DiT backbone.

This is the integration point the whole survey is about: the denoiser is an
iterative map eps_hat = F(x_t, t, c) and the cache policy decides, per
(step, module), between COMPUTE / REUSE / FORECAST.

Modalities: every entry point here dispatches on the config — a plain
isotropic DiT (image latents, audio mel-spectrograms) when
`cfg.dit_num_frames == 0`, the factorized spatio-temporal video DiT
(repro.models.video_dit) otherwise.  Latents are always (B, cfg.dit_tokens,
cfg.dit_in_dim), so the cache/serving stack is modality-agnostic; only the
backbone forward and the TeaCache signal change underneath
(repro.modalities wraps this into named workload specs).

Granularities (survey Fig. 2 reuse-granularity axis):

  MODEL     — one policy gates the full backbone output.  TeaCache's
              input-side signal (the AdaLN-modulated first-block input,
              Eq. 22) is wired through automatically.  This granularity is
              also FreqCa's CRF memory trick: the cache holds one tensor
              regardless of depth (Eq. 52).
  BLOCK     — one policy state per DiT block threaded through the layer scan
              (FORA / Δ-DiT / TaylorSeer per-block operation).
  DEEPCACHE — structural split: the first `shallow_n` blocks always compute
              (DeepCache's "downsampling path"), the remaining deep section
              is gated as one unit (its "upsampling path").  The adaption of
              DeepCache's U-Net insight to the isotropic DiT stack follows
              Δ-DiT's front/rear analysis.
  PAB_VIDEO — video backbone only: Pyramid Attention Broadcast with
              per-module-type ranges — each block's spatial-attention,
              temporal-attention and MLP branch outputs cached and
              broadcast over different intervals (temporal the longest);
              repro.core.temporal.TemporalPABStack owns the layer loop.

Classifier-free guidance (cfg_scale > 0) doubles the compute; the
`cfg_policy` slot accepts FasterCacheCFG to reuse the unconditional branch
(survey §III-C), including its low-frequency cond-residual mode, which
receives the conditional output via `signals["cond_out"]`.  `null_embed`
carries negative-prompt conditioning: an arbitrary (d_model,) vector used
for the unconditional branch instead of the null-class embedding.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.core import (CachePolicy, CachedStack, NoCachePolicy,
                        TemporalPABStack)
from repro.models import dit, video_dit

PyTree = Any


def backbone_module(cfg):
    """The backbone module for this config's modality (dit | video_dit)."""
    return video_dit if cfg.dit_num_frames > 0 else dit


def backbone_fns(cfg):
    """(forward_fn, signal_fn) for this config's modality; both take the
    model params as their first argument, so a jitted caller passes them as
    program operands instead of baking them into the executable.

    forward_fn(params, xs, ts, labels, y_embed=None, txt_kv=None,
    txt_mask=None) -> eps — xs (B, T, D), ts (B,) float timesteps, labels
    (B,) int32 class conditioning, y_embed (B, d) optional conditioning-
    vector override (negative prompts), txt_kv/txt_mask the precomputed
    per-layer text K/V tables + key mask (text-enabled configs; see
    models.dit.text_kv).
    signal_fn(params, xs, ts, labels) -> the TeaCache modulated input
    signal (computed BEFORE the first block, so it is text-independent by
    construction — prompts never perturb the refresh decision).
    """
    mod = backbone_module(cfg)

    def forward_fn(params, xs, ts, labels, y_embed=None, txt_kv=None,
                   txt_mask=None):
        return mod.forward(params, xs, ts.astype(jnp.float32),
                           labels.astype(jnp.int32), cfg, y_embed=y_embed,
                           txt_kv=txt_kv, txt_mask=txt_mask)

    def signal_fn(params, xs, ts, labels):
        h, c = mod.embed_patches(params, xs, ts.astype(jnp.float32),
                                 labels.astype(jnp.int32), cfg)
        return mod.modulated_signal(params, h, c, cfg)

    return forward_fn, signal_fn


def _null_embed_rows(params, nulls, null_vecs, null_mask):
    """Per-row unconditional conditioning: the null-class embedding, replaced
    by the request's negative-prompt vector where `null_mask` is set."""
    ce = params["class_embed"][nulls.astype(jnp.int32)]
    return jnp.where(null_mask[:, None], null_vecs.astype(ce.dtype), ce)


def _as_text(text, cfg):
    """Normalize prompt conditioning to (te (L, d) f32, tm (L,) bool).

    `text` is a repro.conditioning PromptEmbedding, an (embed, mask) pair,
    or None.  Embeddings are zeroed at masked positions — the invariant the
    cross-attention no-op branch relies on (models.dit.cross_attn_branch).
    """
    if text is None:
        return None
    if cfg.dit_text_len <= 0:
        raise ValueError(f"config '{cfg.name}' is not text-enabled "
                         f"(dit_text_len == 0) but a prompt was given")
    te, tm = (text.embed, text.mask) if hasattr(text, "embed") else text
    te = jnp.asarray(te, jnp.float32)
    tm = jnp.asarray(tm, bool)
    if te.ndim == 3:                      # batched (1, L, d) -> (L, d)
        te, tm = te[0], tm[0]
    if te.shape != (cfg.dit_text_len, cfg.d_model):
        raise ValueError(f"prompt embedding shape {te.shape} != "
                         f"({cfg.dit_text_len}, {cfg.d_model})")
    return jnp.where(tm[:, None], te, 0.0), tm


def _text_pooled(text):
    """The pooled (d_model,) view of a normalized (te, tm) pair — the
    vector the CFG negative-prompt (null-vec) path conditions on."""
    te, tm = text
    n = jnp.maximum(jnp.sum(tm), 1)
    return jnp.sum(te, axis=0) / n


class CachedDenoiser:
    """eps_hat = denoiser(state, i, x, t); state threads the cache pytrees."""

    def __init__(self, params, cfg, policy: Optional[CachePolicy] = None,
                 granularity: str = "model", shallow_n: int = 4,
                 cfg_scale: float = 0.0, cfg_policy: Optional[CachePolicy] = None,
                 class_label: int = 0, null_embed=None, text=None,
                 neg_text=None):
        assert granularity in ("model", "block", "deepcache", "pab_video")
        self.params = params
        self.cfg = cfg
        self.policy = policy or NoCachePolicy()
        self.granularity = granularity
        self.shallow_n = shallow_n
        self.cfg_scale = float(cfg_scale)
        self.cfg_policy = cfg_policy
        self.class_label = class_label
        # prompt conditioning (PromptEmbedding or (embed, mask); text-enabled
        # configs only): cross-attn K/V projected ONCE here — text is
        # step-invariant, so no denoise step ever recomputes it
        self._text = _as_text(text, cfg)
        self._neg = _as_text(neg_text, cfg)
        self._text_kv = (None if self._text is None else
                         dit.text_kv(params, self._text[0][None], cfg))
        self._neg_kv = (None if self._neg is None else
                        dit.text_kv(params, self._neg[0][None], cfg))
        # negative-prompt conditioning: an arbitrary (d_model,) vector for the
        # unconditional branch (None = the model's null-class embedding); a
        # neg_text prompt defaults it to the pooled prompt embedding — the
        # same convention the serving engine's null-vec tables use
        if null_embed is None and self._neg is not None:
            null_embed = _text_pooled(self._neg)
        self.null_embed = (None if null_embed is None
                           else jnp.asarray(null_embed, jnp.float32))
        self._mod = backbone_module(cfg)
        if granularity == "block":
            self._stack = CachedStack(
                lambda p, x, c: self._block(p, x, c),
                self.policy, cfg.num_layers)
        elif granularity == "pab_video":
            assert cfg.dit_num_frames > 0, \
                "pab_video granularity needs the factorized video backbone"
            self._stack = TemporalPABStack(video_dit.pab_branch_fns(cfg),
                                           cfg.num_layers)

    # -- text helpers ---------------------------------------------------
    def _text_rows(self, which, B):
        """(te, tm) broadcast to batch B; zero/empty rows when no prompt
        (text-enabled configs run the exact no-op branch then)."""
        if which is not None:
            te, tm = which
        else:
            te = jnp.zeros((self.cfg.dit_text_len, self.cfg.d_model),
                           jnp.float32)
            tm = jnp.zeros((self.cfg.dit_text_len,), bool)
        return (jnp.broadcast_to(te[None], (B,) + te.shape),
                jnp.broadcast_to(tm[None], (B,) + tm.shape))

    def _txt_kwargs(self, kv, which, B):
        """forward() kwargs for the precomputed-K/V path (model/deepcache
        granularity and the uncond branch — full-forward call sites)."""
        if kv is None:
            return {}
        tk, tv = kv
        _, tm = self._text_rows(which, B)
        return {"txt_kv": (jnp.broadcast_to(tk, (B,) + tk.shape[1:]),
                           jnp.broadcast_to(tv, (B,) + tv.shape[1:])),
                "txt_mask": tm}

    def _block(self, p, x, c):
        """One block under the cond-branch text conditioning.  Cache-stack
        scans broadcast their args across layers, so per-layer K/V is
        projected inline from the (step-invariant) prompt embeddings."""
        txt = None
        if self.cfg.dit_text_len > 0:
            te, tm = self._text_rows(self._text, x.shape[0])
            tk, tv = dit.cross_attn_kv(p["cross"], te.astype(x.dtype))
            txt = (tk, tv, tm)
        if self._mod is video_dit:
            return video_dit.video_block(p, x, c, self.cfg, txt=txt)
        return dit.dit_block(p, x, c, self.cfg, txt=txt)

    # ------------------------------------------------------------------
    def init_state(self, batch: int) -> PyTree:
        cfgm = self.cfg
        feat = (batch, cfgm.dit_tokens, cfgm.d_model)
        eps_shape = (batch, cfgm.dit_tokens, cfgm.dit_in_dim)
        if self.granularity == "model":
            try:  # TeaCache tracks an input-side signal of a different shape
                state = {"policy": self.policy.init_state(
                    eps_shape, signal_shape=feat)}
            except TypeError:
                state = {"policy": self.policy.init_state(eps_shape)}
        elif self.granularity in ("block", "pab_video"):
            state = {"policy": self._stack.init(feat)}
        else:  # deepcache: one cache over the deep section's hidden output
            state = {"policy": self.policy.init_state(feat)}
        if self.cfg_policy is not None:
            state["cfg"] = self.cfg_policy.init_state(eps_shape)
        return state

    # ------------------------------------------------------------------
    def _backbone(self, x_lat, t_vec, y, state, step):
        """One conditional forward under the configured granularity.

        Returns (eps_hat, new_policy_state)."""
        params, cfgm, mod = self.params, self.cfg, self._mod

        if self.granularity == "model":
            def compute_fn(lat):
                return mod.forward(params, lat, t_vec, y, cfgm,
                                   **self._txt_kwargs(self._text_kv,
                                                      self._text,
                                                      lat.shape[0]))

            # TeaCache's signal: timestep-modulated first-block input
            h, c = mod.embed_patches(params, x_lat, t_vec, y, cfgm)
            sig = mod.modulated_signal(params, h, c, cfgm)
            return self.policy.apply(state, step, x_lat, compute_fn,
                                     signal=sig)

        h, c = mod.embed_patches(params, x_lat, t_vec, y, cfgm)
        if self.granularity in ("block", "pab_video"):
            if self.granularity == "pab_video" and cfgm.dit_text_len > 0:
                # text-enabled PAB branch fns take (c, te, tm) broadcast args
                te, tm = self._text_rows(self._text, h.shape[0])
                h, new_state = self._stack(state, step, h, params["blocks"],
                                           c, te, tm)
            else:
                h, new_state = self._stack(state, step, h, params["blocks"],
                                           c)
            return mod.final_layer(params, h, c, cfgm), new_state

        # deepcache split
        F = self.shallow_n
        shallow = jax.tree_util.tree_map(lambda a: a[:F], params["blocks"])
        deep = jax.tree_util.tree_map(lambda a: a[F:], params["blocks"])

        def run(h, stacked):
            def body(h, p):
                return self._block(p, h, c), None
            h, _ = jax.lax.scan(body, h, stacked)
            return h

        h = run(h, shallow)
        h, new_state = self.policy.apply(state, step, h,
                                         lambda hh: run(hh, deep))
        return mod.final_layer(params, h, c, cfgm), new_state

    # ------------------------------------------------------------------
    def __call__(self, state, step, x_lat, t_vec):
        B = x_lat.shape[0]
        state = state if state is not None else self.init_state(B)
        y_cond = jnp.full((B,), self.class_label, jnp.int32)
        eps_c, pol_state = self._backbone(x_lat, t_vec, y_cond, state["policy"],
                                          step)
        new_state = {"policy": pol_state}

        if self.cfg_scale > 0.0:
            y_null = jnp.full((B,), self.cfg.dit_num_classes, jnp.int32)
            y_embed = (None if self.null_embed is None
                       else jnp.broadcast_to(self.null_embed[None],
                                             (B, self.cfg.d_model)))
            mod = self._mod

            def plain_uncond(lat):
                # uncond rows attend over the NEGATIVE prompt's K/V (zero
                # tables when none — the classic empty-prompt uncond branch)
                return mod.forward(self.params, lat, t_vec, y_null, self.cfg,
                                   y_embed=y_embed,
                                   **self._txt_kwargs(self._neg_kv,
                                                      self._neg,
                                                      lat.shape[0]))

            if self.cfg_policy is not None:
                # unconditional branch gated by the CFG policy; its compute_fn
                # runs a fresh (non-caching) backbone pass.  cond_out feeds
                # FasterCacheCFG's low-frequency residual reconstruction.
                eps_u, cstate = self.cfg_policy.apply(state["cfg"], step, x_lat,
                                                      plain_uncond,
                                                      cond_out=eps_c)
                new_state["cfg"] = cstate
            else:
                eps_u = plain_uncond(x_lat)
            eps_c = eps_u + self.cfg_scale * (eps_c - eps_u)

        return eps_c, new_state


def slot_denoise_fns(cfg, policy: CachePolicy):
    """Slot-parallel CachedDenoiser entry point (model granularity).

    The serving engine (repro.serving.diffusion) advances many concurrent
    requests, each at its own denoising step with its own cache state,
    through one compiled program.  The split that makes this fast:

      backbone_fn(params, xs, ts, labels) -> eps   plain SLOT-BATCHED
          forward — the slot axis IS the model's batch axis, so XLA sees the
          same program as uncached batched inference.  (Running the
          backbone inside vmap instead would thread a singleton batch dim
          through every matmul, which knocks XLA CPU off its fast paths.)
      apply_fn(params, state, step, x, t, label, y_full) -> (eps, state)
          per-slot policy logic, vmapped by the engine over everything but
          `params`.  `y_full` is this slot's row of backbone_fn's output;
          the compute branch selects it into the cache, other branches
          reuse/forecast.  Every repro.core policy calls compute_fn on
          exactly its input x, so precomputing F(x) outside the branch is
          semantics-preserving.  On skip ticks the engine passes zeros for
          y_full — ONLY safe when the policy's want_compute is False for
          every slot (lax.cond vmaps to a select, so the dummy branch's
          outputs are discarded; slot_want_fns is the traced mirror of
          that decision).

    Every function takes the model params as its first argument (program
    operands, never closed-over constants).  x: (T, in_dim) latent tokens;
    t: scalar model-facing timestep; label: scalar int32 class
    conditioning.  The backbone is the config's modality backbone
    (image/audio DiT or factorized video DiT); TeaCache's input-side signal
    (the AdaLN-modulated first-block input, Eq. 22) is wired through when
    the policy declares `uses_signal`.
    """
    forward_fn, signal_fn = backbone_fns(cfg)

    def backbone_fn(params, xs, ts, labels, txt=None):
        """txt: the engine's per-slot text-table dict ({} / None = no text;
        an EMPTY dict contributes zero jit operand leaves, so text-free
        engines keep the exact pre-text program signature).  Cond rows
        attend over k/v/mask — K/V were projected once at admission."""
        if not txt:
            return forward_fn(params, xs, ts, labels)
        return forward_fn(params, xs, ts, labels,
                          txt_kv=(txt["k"], txt["v"]), txt_mask=txt["mask"])

    def apply_fn(params, state, step, x, t, label, y_full):
        xb = x[None]
        sig = {}
        if policy.uses_signal:           # skip-tick cost: don't embed
            t_vec = jnp.reshape(t, (1,)).astype(jnp.float32)
            y = jnp.reshape(label, (1,)).astype(jnp.int32)
            sig = {"signal": signal_fn(params, xb, t_vec, y)}
        eps, state = policy.apply(state, step, xb, lambda _: y_full[None],
                                  **sig)
        return eps[0], state

    return backbone_fn, apply_fn


def slot_cfg_denoise_fns(cfg, policy: CachePolicy,
                         cfg_policy: Optional[CachePolicy] = None):
    """CFG-aware slot-parallel entry point for the serving engine.

    Extends `slot_denoise_fns` to guided requests: each slot carries a
    conditional cache state (the main `policy`) *and* an unconditional-branch
    state (`cfg_policy`, typically FasterCacheCFG; None means the uncond
    branch recomputes every step — naive two-branch serving).  The backbone
    still runs OUTSIDE vmap; on both-branch ticks the engine stacks cond and
    uncond rows into one 2S-row batch (slot axis == batch axis), so XLA sees
    a plain batched forward either way.  Every function takes the model
    params first.

      backbone2_fn(params, xs, ts, labels, null_labels, null_vecs, null_mask)
          one 2S-row backbone pass over [cond rows; uncond rows], split back
          into the two S-row branch outputs.  `null_vecs` (S, d_model) with
          `null_mask` (S,) carry per-slot negative-prompt conditioning
          vectors that replace the null-class embedding on uncond rows.
      backbone_fn(params, xs, ts, labels) -> eps_c
          the S-row cond-only pass (from slot_denoise_fns), dispatched on
          ticks where every active slot reuses its cached uncond branch —
          this is where FasterCacheCFG's serving-level saving comes from.
      apply_fn(params, state, step, x, t, label, scale, cfg_w, y_c, y_u)
          per-slot (vmapped) policy logic over the combined state
          {"policy": ..., "cfg": ...}.  `scale` is the slot's cfg_scale
          (<= 0 means unguided: the uncond branch output is discarded via a
          select, never blended).  `cfg_w` is the slot's trajectory-progress
          weight step/(num_steps-1) — passed from the host because slots run
          different step budgets against one shared FasterCacheCFG instance.
          The cond-branch output is forwarded to the CFG policy as
          `cond_out` (FasterCacheCFG's low-frequency residual mode).
          On cond-only / skip ticks the engine passes zeros for the missing
          y_u / y_c rows — safe under the same rule as slot_denoise_fns:
          a dummy row may only reach a branch that the per-slot lax.cond
          (vmapped to a select) discards.
    """
    uncond_policy = cfg_policy if cfg_policy is not None else NoCachePolicy()
    forward_fn, _ = backbone_fns(cfg)
    backbone_fn, base_apply = slot_denoise_fns(cfg, policy)

    def backbone2_fn(params, xs, ts, labels, null_labels, null_vecs,
                     null_mask, txt=None):
        S = xs.shape[0]
        x2 = jnp.concatenate([xs, xs], axis=0)
        t2 = jnp.concatenate([ts, ts], axis=0).astype(jnp.float32)
        y2 = jnp.concatenate([labels, null_labels], axis=0).astype(jnp.int32)
        ce_c = params["class_embed"][labels.astype(jnp.int32)]
        ce_u = _null_embed_rows(params, null_labels, null_vecs, null_mask)
        kw = {}
        if txt:
            # cond rows attend the prompt's K/V, uncond rows the NEGATIVE
            # prompt's (nk/nv; all-masked when the request carries none)
            kw = {"txt_kv": (jnp.concatenate([txt["k"], txt["nk"]], axis=0),
                             jnp.concatenate([txt["v"], txt["nv"]], axis=0)),
                  "txt_mask": jnp.concatenate([txt["mask"], txt["nmask"]],
                                              axis=0)}
        eps = forward_fn(params, x2, t2, y2,
                         y_embed=jnp.concatenate([ce_c, ce_u], axis=0), **kw)
        return eps[:S], eps[S:]

    def apply_fn(params, state, step, x, t, label, scale, cfg_w, y_c, y_u):
        eps_c, pol_state = base_apply(params, state["policy"], step, x, t,
                                      label, y_c)
        eps_u, cfg_state = uncond_policy.apply(state["cfg"], step, x[None],
                                               lambda _: y_u[None],
                                               cfg_w=cfg_w,
                                               cond_out=eps_c[None])
        eps_u = eps_u[0]
        eps = jnp.where(scale > 0.0, eps_u + scale * (eps_c - eps_u), eps_c)
        return eps, {"policy": pol_state, "cfg": cfg_state}

    return backbone2_fn, backbone_fn, apply_fn


def slot_compact_denoise_fns(cfg, policy: CachePolicy,
                             cfg_policy: Optional[CachePolicy] = None):
    """Row-compacted slot-parallel entry point for the serving engine.

    `slot_cfg_denoise_fns` runs the backbone over *whole-pool* batches: S cond
    rows, optionally doubled to 2S when any slot wants an uncond refresh.
    That makes tick cost all-or-nothing — one TeaCache slot firing drags every
    slot through the backbone.  This variant adds the gather/scatter pair that
    lets the engine dispatch the backbone over EXACTLY the rows whose per-slot
    policies want a compute this tick, padded to a power-of-two bucket so the
    jit program count stays bounded (one program per bucket size):

      compact_backbone_fn(params, xs, tvals, labels, nulls, null_vecs,
                          null_mask, txt, row_slot, row_uncond, row_dest)
                          -> (y_c, y_u)
          `row_slot` (B,) gathers each compacted row's latent/timestep from
          its source slot; `row_uncond` selects the null label (or the
          slot's negative-prompt vector, where `null_mask` is set) for
          uncond rows; the backbone runs over the compacted (B, T, D) batch;
          the scatter writes each row into a (2S+1)-row buffer at `row_dest`
          (cond row i -> i, uncond row i -> S + i, padding -> the 2S dump
          row) and splits it back into the S-row `y_c` / `y_u` layout the
          vmapped apply_fn expects.  Rows that were not gathered come back
          as zeros — safe under the standing invariant that a dummy row may
          only reach a branch the per-slot lax.cond (vmapped to a select)
          discards, i.e. the gather set must cover every row whose policy
          `want_compute` is True.
      backbone2_fn / backbone_fn / apply_fn
          unchanged from `slot_cfg_denoise_fns` — compaction only changes
          how y_c / y_u are produced, never the per-slot policy step.

    All index operands are traced values, so one jit program per bucket size
    B serves every gather pattern of that size.  B is static per program:
    the engine re-pads each tick's row set to the next power of two.
    """
    forward_fn, _ = backbone_fns(cfg)
    backbone2_fn, backbone_fn, apply_fn = slot_cfg_denoise_fns(
        cfg, policy, cfg_policy)

    def compact_backbone_fn(params, xs, tvals, labels, nulls, null_vecs,
                            null_mask, txt, row_slot, row_uncond, row_dest):
        S, T, D = xs.shape
        xb = xs[row_slot]
        tb = tvals[row_slot].astype(jnp.float32)
        yb = jnp.where(row_uncond, nulls[row_slot],
                       labels[row_slot]).astype(jnp.int32)
        # negative-prompt rows: uncond rows of slots carrying a vector
        ce = _null_embed_rows(params, yb, null_vecs[row_slot],
                              jnp.logical_and(row_uncond,
                                              null_mask[row_slot]))
        kw = {}
        if txt:
            # per-row text tables: cond rows gather the slot's prompt K/V,
            # uncond rows its negative-prompt K/V
            sel = row_uncond[:, None, None, None]
            kw = {"txt_kv": (jnp.where(sel, txt["nk"][row_slot],
                                       txt["k"][row_slot]),
                             jnp.where(sel, txt["nv"][row_slot],
                                       txt["v"][row_slot])),
                  "txt_mask": jnp.where(row_uncond[:, None],
                                        txt["nmask"][row_slot],
                                        txt["mask"][row_slot])}
        eps = forward_fn(params, xb, tb, yb, y_embed=ce, **kw)
        # scatter: padding rows all land in the 2S dump row and are dropped
        buf = jnp.zeros((2 * S + 1, T, D), eps.dtype).at[row_dest].set(eps)
        return buf[:S], buf[S:2 * S]

    return compact_backbone_fn, backbone2_fn, backbone_fn, apply_fn


def slot_want_fns(cfg, policy: CachePolicy,
                  cfg_policy: Optional[CachePolicy] = None):
    """Fused slot-batched want/metric pass for the serving engine's planner.

    Computing a signal-using policy's TeaCache signal per slot on a
    SINGLETON batch inside vmap would thread a batch-1 dim through the
    modulated-embed matmuls, and planning the cond and uncond branches
    separately would cost two device syncs per tick.  This entry point
    fuses the whole plan into one program:

      want_all_fn(params, states, steps, xs, tvals, labels, guided)
          -> (want_cond, want_uncond, metric)     each (S,)

    The TeaCache signal is computed ONCE over the whole (S, T, D) slot batch
    outside vmap (slot axis == batch axis, same layout as the backbone
    call), then handed row-wise to the vmapped per-slot predicates.  The
    batched embed is row-independent, so each slot sees exactly the signal
    the singleton path produced.  `want_uncond` is masked by the slot's
    `guided` flag, so pure-unguided pools never dispatch uncond rows.
    `metric` is the per-slot `CachePolicy.want_metric` scalar (the value
    the refresh decision thresholds on — TeaCache's corrected accumulated
    distance, the LazyDiT gate score, 0 for schedule-only policies), which
    the control plane's SignalTraceLog records; it rides the same device
    round trip, so trace logging costs no extra sync."""
    uncond_policy = cfg_policy if cfg_policy is not None else NoCachePolicy()
    _, signal_fn = backbone_fns(cfg)

    def per_slot(state, step, x, sig, g):
        xb = x[None]
        kw = {"signal": sig[None]} if policy.uses_signal else {}
        wc = policy.want_compute(state["policy"], step, xb, **kw)
        wu = uncond_policy.want_compute(state["cfg"], step, xb)
        m = jnp.asarray(policy.want_metric(state["policy"], step, xb, **kw),
                        jnp.float32)
        # `& step >= 0` / `+ 0 * step` keep constant outputs mapped under
        # vmap (schedule-only policies return trace-constant predicates)
        wc = jnp.logical_and(jnp.asarray(wc), step >= 0)
        wu = jnp.logical_and(jnp.logical_and(jnp.asarray(wu), g), step >= 0)
        return wc, wu, m + 0.0 * step.astype(jnp.float32)

    def want_all_fn(params, states, steps, xs, tvals, labels, guided):
        if policy.uses_signal:
            sigs = signal_fn(params, xs, tvals.astype(jnp.float32),
                             labels.astype(jnp.int32))
        else:                            # dummy rows: per_slot never reads them
            sigs = jnp.zeros((xs.shape[0], 1, 1), jnp.float32)
        return jax.vmap(per_slot)(states, steps, xs, sigs, guided)

    return want_all_fn


def cfg_denoise_fn(params, cfg, cfg_scale: float, class_label: int = 0,
                   null_embed=None, text=None, neg_text=None):
    """Uncached CFG denoiser (the exact baseline): eps = e_u + s (e_c - e_u).

    `null_embed` (d_model,) replaces the null-class embedding with an
    arbitrary negative-prompt conditioning vector.  `text` / `neg_text`
    (PromptEmbedding or (embed, mask); text-enabled configs) condition the
    cond / uncond branch through cross-attention; K/V are projected once at
    construction, and a neg_text prompt defaults `null_embed` to its pooled
    embedding — the same convention CachedDenoiser and the engine use.

    The backbone call is jitted with the params as an operand: run eagerly,
    its layer scan would re-trace and re-compile on every step."""
    forward_fn = jax.jit(backbone_fns(cfg)[0])
    txt = _as_text(text, cfg)
    neg = _as_text(neg_text, cfg)
    txt_kv = None if txt is None else dit.text_kv(params, txt[0][None], cfg)
    neg_kv = None if neg is None else dit.text_kv(params, neg[0][None], cfg)
    if null_embed is None and neg is not None:
        null_embed = _text_pooled(neg)
    ne = None if null_embed is None else jnp.asarray(null_embed, jnp.float32)

    def _kw(kv, pair, B):
        if kv is None:
            return {}
        tk, tv = kv
        return {"txt_kv": (jnp.broadcast_to(tk, (B,) + tk.shape[1:]),
                           jnp.broadcast_to(tv, (B,) + tv.shape[1:])),
                "txt_mask": jnp.broadcast_to(pair[1][None],
                                             (B,) + pair[1].shape)}

    def fn(state, step, x, t_vec):
        B = x.shape[0]
        y_c = jnp.full((B,), class_label, jnp.int32)
        y_u = jnp.full((B,), cfg.dit_num_classes, jnp.int32)
        e_c = forward_fn(params, x, t_vec, y_c, **_kw(txt_kv, txt, B))
        if cfg_scale <= 0.0:
            return e_c, state
        ye = None if ne is None else jnp.broadcast_to(ne[None],
                                                      (B, cfg.d_model))
        e_u = forward_fn(params, x, t_vec, y_u, y_embed=ye,
                         **_kw(neg_kv, neg, B))
        return e_u + cfg_scale * (e_c - e_u), state
    return fn
