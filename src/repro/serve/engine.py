"""Batched serving engines: prefill + greedy/temperature decode.

Two engines share the model's pure functions:

- :class:`DecodeEngine` — the whole-batch reference: contiguous
  ``(L, B, C, Hkv, D)`` KV cache, one jitted fused sample+decode step
  (PRNG split and sampling INSIDE the jit, cache donated), static batch.
  Kept as the parity oracle for the paged engine's tests.
- :class:`PagedDecodeEngine` — the production tier: page-pool KV cache
  with per-sequence block tables, ONE decode step jitted over fixed
  (max_batch, pool) shapes so continuous-batching admissions/evictions
  never retrace (asserted via :attr:`step_traces`), fused sampling, and
  an on-device output buffer (zero per-token host syncs). Weight
  hot-swap is a host pointer swap (``set_params``) between steps — the
  step takes params as an argument, so new weights apply from the next
  step with zero downtime and zero retrace.

Works for every architecture family (KV caches, SSM states, hybrid,
multi-codebook audio). MusicGen's codebook *delay pattern* (codebook c is
shifted c steps so step t emits codebook c's frame t-c) is applied here,
in the engine — the model itself sees plain parallel streams.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import transformer as tfm
from repro.models.cache import paged_table_width
from repro.models.registry import (LM, _prefix_len, lm_paged_decode_step,
                                   lm_paged_prefill_chunk,
                                   lm_paged_prefix_fill)
from repro.serve.pages import PageManager


def apply_delay_pattern(tokens, pad_token: int = 0):
    """(B, S, CB) -> (B, S+CB-1, CB) with codebook c delayed by c steps."""
    B, S, CB = tokens.shape
    out = jnp.full((B, S + CB - 1, CB), pad_token, tokens.dtype)
    for c in range(CB):
        out = out.at[:, c:c + S, c].set(tokens[..., c])
    return out


def undo_delay_pattern(tokens, n_frames: int):
    """(B, S+CB-1, CB) -> (B, n_frames, CB)."""
    CB = tokens.shape[-1]
    cols = [tokens[:, c:c + n_frames, c] for c in range(CB)]
    return jnp.stack(cols, axis=-1)


def _sample(logits, key, temperature: float):
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1)
    return jax.random.categorical(key, logits / temperature, axis=-1)


@dataclasses.dataclass
class DecodeEngine:
    """Whole-batch reference engine (static batch, contiguous cache)."""
    lm: LM
    params: object
    max_seq_len: int
    rules: object = None

    def __post_init__(self):
        self._prefill = jax.jit(
            lambda p, c, b: self.lm.prefill(p, c, b, rules=self.rules),
            donate_argnums=(1,))
        self._steps = {}     # temperature (static) -> fused jitted step

    def _fused_step(self, temperature: float):
        """sample(prev logits) + decode in ONE dispatch: the PRNG split
        happens inside the jit and the cache is donated, so temperature>0
        decode costs no host-side split and no cache re-allocation."""
        if temperature not in self._steps:
            def step(params, cache, logits, key):
                key, sub = jax.random.split(key)
                tok = _sample(logits, sub, temperature)
                logits, cache = self.lm.decode_step(params, cache, tok,
                                                    rules=self.rules)
                return tok, logits, cache, key
            self._steps[temperature] = jax.jit(step, donate_argnums=(1, 2))
        return self._steps[temperature]

    def generate(self, batch, n_new_tokens: int, *, temperature: float = 0.0,
                 seed: int = 0):
        """Prefill ``batch`` then decode ``n_new_tokens`` greedily/sampled.

        Returns generated tokens: (B, n_new) or (B, n_new, CB) for audio.
        """
        B = batch["tokens"].shape[0]
        cache, _ = self.lm.init_cache(B, self.max_seq_len)
        logits, cache = self._prefill(self.params, cache, batch)
        key = jax.random.key(seed)
        step = self._fused_step(temperature)
        outs = []
        for _ in range(n_new_tokens):
            tok, logits, cache, key = step(self.params, cache, logits, key)
            outs.append(tok)
        return jnp.stack(outs, axis=1)


# ------------------------------------------------------------------
# paged continuous-batching engine
# ------------------------------------------------------------------


def model_table_width(cfg, max_seq_len: int, page_size: int) -> int:
    """ONE table width per model: the max over the pattern's attention
    specs (a global layer forces full history; pure-windowed patterns get
    the small ring). 1 for attention-free stacks (tables unused)."""
    widths = [paged_table_width(max_seq_len, s.window, page_size)
              for s in tfm.block_pattern(cfg) if s.kind in ("attn", "hybrid")]
    return max(widths) if widths else 1


def needs_exact_prefill(cfg) -> bool:
    """Recurrent stacks (mamba/mLSTM/sLSTM) cannot absorb pad tokens in a
    chunked prefill — the engine routes them through prefix-fill +
    step-prefill instead."""
    return any(s.kind in ("hybrid", "mlstm", "slstm")
               for s in tfm.block_pattern(cfg))


@dataclasses.dataclass
class PagedDecodeEngine:
    """Fixed-shape continuous-batching engine over a paged KV pool.

    ``max_seq_len`` bounds TOTAL tokens per sequence (prefix + prompt +
    generated); ``max_new`` bounds generated tokens (sizes the on-device
    output buffer); ``prefill_chunk`` is the static padded prompt length
    of the chunk-prefill jit. ``temperature`` is static per engine (a
    different temperature is a different program).

    The host side drives :meth:`step` with small per-step control arrays
    (block tables, per-slot positions, prompt-feed masks, output
    indices); all token-rate state (caches, last sampled token, output
    buffer, PRNG key) stays on device and is donated through the single
    jitted step.
    """
    lm: LM
    params: object
    max_batch: int
    max_seq_len: int
    max_new: int
    page_size: int = 4
    n_pages: int | None = None
    prefill_chunk: int = 32
    temperature: float = 0.0
    seed: int = 0

    def __post_init__(self):
        cfg = self.lm.cfg
        self.table_width = model_table_width(cfg, self.max_seq_len,
                                             self.page_size)
        if self.n_pages is None:
            self.n_pages = 1 + self.max_batch * self.table_width
        self.needs_exact_prefill = needs_exact_prefill(cfg)
        self.prefix_len = _prefix_len(cfg)
        self._step_traces = 0
        self._prefill_traces = 0
        self.last_logits = None       # (B, V) f32 of the latest step
        self._jit_step = self._build_step()
        self._jit_prefill = self._build_prefill()
        self._jit_prefix = self._build_prefix_fill()
        self.reset_state(self.seed)

    # ------------------------------------------------------------ state

    def _tok_shape(self):
        cfg = self.lm.cfg
        return (self.max_batch, cfg.n_codebooks) if cfg.family == "audio" \
            else (self.max_batch,)

    def reset_state(self, seed: int = 0):
        """Fresh caches / output buffer / PRNG key / page manager."""
        cfg = self.lm.cfg
        caches, _ = self.lm.init_paged_cache(self.max_batch, self.n_pages,
                                             self.page_size)
        out_shape = (self.max_batch, self.max_new + 1)    # last col = scratch
        if cfg.family == "audio":
            out_shape += (cfg.n_codebooks,)
        self.state = {
            "caches": caches,
            "last": jnp.zeros(self._tok_shape(), jnp.int32),
            "out": jnp.zeros(out_shape, jnp.int32),
            "key": jax.random.key(seed),
        }
        self.pages = PageManager(self.n_pages, self.page_size,
                                 self.table_width, self.max_batch)

    @property
    def scratch_idx(self) -> int:
        """Output column absorbing non-emitting steps (prompt feed)."""
        return self.max_new

    @property
    def step_traces(self) -> int:
        """Times the decode step actually traced — the structural
        no-retrace guarantee is ``step_traces == 1`` after any run."""
        return self._step_traces

    # ------------------------------------------------------------- jits

    def _build_step(self):
        cfg, ps, temp = self.lm.cfg, self.page_size, self.temperature

        def step(params, caches, last, out, key, ctrl):
            self._step_traces += 1        # host side effect: counts traces
            caches = tfm.reset_paged_states(caches, ctrl["reset"])
            up = ctrl["use_prompt"]
            upb = up if last.ndim == 1 else up[:, None]
            tok_in = jnp.where(upb, ctrl["prompt_tok"], last)
            logits, caches = lm_paged_decode_step(
                cfg, params, caches, tok_in, ctrl["pos"], ctrl["tables"], ps)
            key, sub = jax.random.split(key)
            sampled = _sample(logits, sub, temp).astype(jnp.int32)
            out = out.at[jnp.arange(out.shape[0]),
                         ctrl["out_idx"]].set(sampled)
            return caches, sampled, out, key, logits

        return jax.jit(step, donate_argnums=(1, 2, 3))

    def _build_prefill(self):
        cfg, ps, temp = self.lm.cfg, self.page_size, self.temperature

        def prefill(params, caches, last, out, key, batch, n_valid, slot,
                    tables):
            self._prefill_traces += 1
            logits, caches = lm_paged_prefill_chunk(
                cfg, params, caches, batch, n_valid, slot, tables, ps)
            key, sub = jax.random.split(key)
            sampled = _sample(logits, sub, temp).astype(jnp.int32)[0]
            last = last.at[slot].set(sampled)
            out = out.at[slot, 0].set(sampled)
            return caches, last, out, key

        return jax.jit(prefill, donate_argnums=(1, 2, 3))

    def _build_prefix_fill(self):
        cfg, ps = self.lm.cfg, self.page_size

        def prefix_fill(params, caches, slot, tables):
            return lm_paged_prefix_fill(cfg, params, caches, slot, tables, ps)

        return jax.jit(prefix_fill, donate_argnums=(1,))

    # ------------------------------------------------------- host driver

    def set_params(self, new_params):
        """Weight hot-swap: the step takes params as an argument, so the
        next :meth:`step` runs the new weights — no retrace (identical
        shapes/dtypes), no downtime, in-flight state untouched."""
        self.params = new_params

    def step(self, ctrl: dict):
        """One fixed-shape decode step. ``ctrl`` holds host-built arrays:
        tables (B,TW) i32, pos (B,) i32, use_prompt (B,) bool,
        prompt_tok (B,)/(B,CB) i32, out_idx (B,) i32, reset (B,) bool.
        The step's f32 logits stay on device as ``last_logits``."""
        s = self.state
        dev_ctrl = {k: jnp.asarray(v) for k, v in ctrl.items()}
        caches, last, out, key, self.last_logits = self._jit_step(
            self.params, s["caches"], s["last"], s["out"], s["key"], dev_ctrl)
        self.state = {"caches": caches, "last": last, "out": out, "key": key}

    def prefill_into(self, slot: int, batch1: dict, n_valid: int):
        """Chunk-prefill one slot (attention-only stacks): pads the
        prompt to ``prefill_chunk``, writes its pages, samples the first
        output token into ``out[slot, 0]``. One dispatch per admission."""
        tokens = np.asarray(batch1["tokens"])
        S = tokens.shape[1]
        assert S <= self.prefill_chunk, (S, self.prefill_chunk)
        pad = self.prefill_chunk - S
        if pad:
            width = [(0, 0), (0, pad)] + [(0, 0)] * (tokens.ndim - 2)
            tokens = np.pad(tokens, width)
        padded = dict(batch1)
        padded["tokens"] = jnp.asarray(tokens)
        s = self.state
        caches, last, out, key = self._jit_prefill(
            self.params, s["caches"], s["last"], s["out"], s["key"], padded,
            jnp.asarray(n_valid, jnp.int32), jnp.asarray(slot, jnp.int32),
            jnp.asarray(self.pages.tables))
        self.state = {"caches": caches, "last": last, "out": out, "key": key}

    def prefix_fill_into(self, slot: int):
        """Run the learned prefix (meta tokens) for one slot — the exact
        static-length entry point for recurrent stacks."""
        s = self.state
        caches = self._jit_prefix(self.params, s["caches"],
                                  jnp.asarray(slot, jnp.int32),
                                  jnp.asarray(self.pages.tables))
        self.state = dict(s, caches=caches)

    def read_out(self, slot: int, n: int) -> np.ndarray:
        """Fetch one finished request's tokens — a single device→host
        copy per REQUEST, never per token."""
        return np.asarray(self.state["out"][slot, :n])

    def apply_page_perm(self, perm: np.ndarray):
        """Re-gather the device pools after ``PageManager.defrag``:
        ``perm[old] = new`` ⇒ ``new_pool[new] = old_pool[old]``."""
        inv = np.argsort(perm)
        gather = jnp.asarray(inv)

        def regather(c):
            if "pages" not in c:
                return c
            return dict(c, pages={k: v[:, gather]
                                  for k, v in c["pages"].items()})

        self.state = dict(self.state,
                          caches=[regather(c) for c in self.state["caches"]])

    def generate(self, batch, n_new_tokens: int, *, seed: int = 0):
        """Whole-batch convenience wrapper (parity with
        :meth:`DecodeEngine.generate` at temperature 0): admits all B
        sequences through the continuous scheduler at once."""
        from repro.serve.scheduler import ContinuousScheduler, Request
        B = batch["tokens"].shape[0]
        reqs = []
        for b in range(B):
            vis = np.asarray(batch["vis_embeds"][b]) \
                if "vis_embeds" in batch else None
            reqs.append(Request(rid=b, tokens=np.asarray(batch["tokens"][b]),
                                n_new=n_new_tokens, vis_embeds=vis))
        outs = ContinuousScheduler(self).run(reqs, seed=seed)
        return jnp.asarray(np.stack([outs[b] for b in range(B)], axis=0))


def make_paged_decode_bundle(lm: LM, *, max_batch: int = 2,
                             max_seq_len: int = 64, max_new: int = 4,
                             page_size: int = 4, n_pages: int | None = None,
                             temperature: float = 0.0):
    """The paged decode step as a :class:`StepBundle` for the static
    contract checker: single-device serving step — no collectives
    anywhere, exact Pallas-launch budget (1 paged-attention launch per
    pattern attention spec under ``flash_pallas``, 0 otherwise), donated
    caches/token/output buffers, no f64."""
    from repro.analysis.contracts import decode_contract
    from repro.launch.sync.bundles import StepBundle

    cfg = lm.cfg
    TW = model_table_width(cfg, max_seq_len, page_size)
    n_pages = n_pages if n_pages is not None else 1 + max_batch * TW

    def step(params, caches, last, out, key, ctrl):
        caches = tfm.reset_paged_states(caches, ctrl["reset"])
        up = ctrl["use_prompt"]
        upb = up if last.ndim == 1 else up[:, None]
        tok_in = jnp.where(upb, ctrl["prompt_tok"], last)
        logits, caches = lm_paged_decode_step(
            cfg, params, caches, tok_in, ctrl["pos"], ctrl["tables"],
            page_size)
        key, sub = jax.random.split(key)
        sampled = _sample(logits, sub, temperature).astype(jnp.int32)
        out = out.at[jnp.arange(out.shape[0]), ctrl["out_idx"]].set(sampled)
        return caches, sampled, out, key

    params_abs, _ = lm.abstract()
    caches_abs = jax.eval_shape(
        lambda: lm.init_paged_cache(max_batch, n_pages, page_size)[0])
    tokf = (max_batch, cfg.n_codebooks) if cfg.family == "audio" \
        else (max_batch,)
    out_shape = tokf[:1] + (max_new + 1,) + tokf[1:]
    ctrl_abs = {
        "tables": jax.ShapeDtypeStruct((max_batch, TW), jnp.int32),
        "pos": jax.ShapeDtypeStruct((max_batch,), jnp.int32),
        "use_prompt": jax.ShapeDtypeStruct((max_batch,), jnp.bool_),
        "prompt_tok": jax.ShapeDtypeStruct(tokf, jnp.int32),
        "out_idx": jax.ShapeDtypeStruct((max_batch,), jnp.int32),
        "reset": jax.ShapeDtypeStruct((max_batch,), jnp.bool_),
    }
    abstract_args = (
        params_abs, caches_abs,
        jax.ShapeDtypeStruct(tokf, jnp.int32),
        jax.ShapeDtypeStruct(out_shape, jnp.int32),
        jax.eval_shape(lambda: jax.random.key(0)),
        ctrl_abs,
    )
    n_attn = sum(1 for s in tfm.block_pattern(cfg)
                 if s.kind in ("attn", "hybrid"))
    launches = n_attn if cfg.attn_impl == "flash_pallas" else 0
    return StepBundle(
        fn=step, abstract_args=abstract_args, in_shardings=None,
        out_shardings=None, donate_argnums=(1, 2, 3),
        contract=decode_contract(
            launches=launches,
            notes="paged continuous-batching decode step (serving tier)"))
