"""LM assembly: embeddings → scanned stack → head, per architecture family.

``build_model(cfg)`` returns an :class:`LM` — a bundle of pure functions —
plus logical-dim pytrees for the sharding rules. ``LM.abstract()`` gives
(param ShapeDtypeStructs, dims) without allocating, which is what the
multi-pod dry-run lowers against.

Batch conventions (targets included in the batch dict):
  dense/moe/ssm/hybrid : tokens (B,S) int32, targets (B,S)
  vlm                  : + vis_embeds (B, n_vis, d_vis) stub frontend
  audio                : tokens/targets (B,S,n_codebooks) EnCodec streams
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import numpy as np
import jax.numpy as jnp

from repro.common.compat import shard_map
from repro.models import transformer as tfm
from repro.models.common import apply_norm, init_norm, normal_init, softcap
from repro.models.types import ModelConfig


def _dtype(cfg):
    return jnp.dtype(cfg.dtype)


def init_lm(cfg: ModelConfig, key):
    # NOTE: embed/head tables are sharded on vocab (model axis) ONLY — no
    # data-axis FSDP dim. FSDP-sharding them makes XLA all-gather the full
    # table around the token gather / dembed scatter (~19 GB/device fixed
    # overhead measured in the dry-run); vocab-sharded tables lower to the
    # megatron-style local-gather + psum instead.
    dtype = _dtype(cfg)
    ks = jax.random.split(key, 6)
    params, dims = {}, {}
    if cfg.family == "audio":
        params["embed"], dims["embed"] = normal_init(
            ks[0], (cfg.n_codebooks, cfg.vocab_size, cfg.d_model),
            (None, "vocab", None), dtype, fan_in=cfg.d_model)
        params["head"], dims["head"] = normal_init(
            ks[1], (cfg.n_codebooks, cfg.d_model, cfg.vocab_size),
            (None, None, "vocab"), dtype, fan_in=cfg.d_model)
    else:
        params["embed"], dims["embed"] = normal_init(
            ks[0], (cfg.vocab_size, cfg.d_model),
            ("vocab", None), dtype, fan_in=cfg.d_model)
        params["head"], dims["head"] = normal_init(
            ks[1], (cfg.d_model, cfg.vocab_size),
            (None, "vocab"), dtype, fan_in=cfg.d_model)
    if cfg.family == "vlm":
        params["vis_proj"], dims["vis_proj"] = normal_init(
            ks[2], (cfg.d_vis, cfg.d_model), (None, "embed"), dtype,
            fan_in=cfg.d_vis)
    if cfg.n_meta_tokens:
        params["meta"], dims["meta"] = normal_init(
            ks[3], (cfg.n_meta_tokens, cfg.d_model), (None, "embed"), dtype,
            fan_in=cfg.d_model)
    params["stack"], dims["stack"] = tfm.init_stack(cfg, ks[4], dtype)
    params["ln_f"], dims["ln_f"] = init_norm(cfg)
    return params, dims


def _sharded_gather(embed, tokens, rules):
    """Megatron-style vocab-sharded embedding lookup (explicit shard_map).

    XLA's auto-partitioned gather/scatter on a vocab-sharded table
    materializes the full f32 table per device (4×8.4 GB for the 256k-vocab
    archs, measured in the dry-run). Each shard instead gathers its
    in-range ids locally and psums — the backward is a *local* scatter
    into the local table shard.
    """
    from jax.sharding import PartitionSpec as P

    mesh = rules.mesh
    vaxis = rules.rules.get("vocab", ())
    vaxis = vaxis[0] if vaxis and vaxis[0] in mesh.shape else None
    if vaxis is None or embed.shape[0] % mesh.shape[vaxis]:
        return jnp.take(embed, tokens, axis=0)
    batch_axes = tuple(a for a in rules.rules.get("batch", ())
                       if a in mesh.shape)
    bsz = int(np.prod([mesh.shape[a] for a in batch_axes])) if batch_axes else 1
    bspec = batch_axes if (bsz and tokens.shape[0] % bsz == 0) else ()

    def local(emb, ids):
        vl = emb.shape[0]
        off = jax.lax.axis_index(vaxis) * vl
        lid = ids - off
        ok = (lid >= 0) & (lid < vl)
        out = jnp.take(emb, jnp.clip(lid, 0, vl - 1), axis=0)
        out = jnp.where(ok[..., None], out, 0)
        return jax.lax.psum(out, vaxis)

    tok_rest = (None,) * (tokens.ndim - 1)
    in_specs = (P(vaxis), P(bspec if bspec else None, *tok_rest))
    out_specs = P(bspec if bspec else None, *tok_rest, None)
    fn = shard_map(local, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    return fn(embed, tokens)


def _embed_tokens(cfg, params, tokens, rules=None):
    if rules is not None:
        gather = functools.partial(_sharded_gather, rules=rules)
    else:
        gather = lambda e, t: jnp.take(e, t, axis=0)
    if cfg.family == "audio":
        # embed: (CB, V, D); tokens (B, S, CB) -> sum over codebooks
        parts = [gather(params["embed"][c], tokens[..., c])
                 for c in range(cfg.n_codebooks)]
        return sum(parts)
    return gather(params["embed"], tokens)


def _prefix_len(cfg) -> int:
    n = cfg.n_meta_tokens
    if cfg.family == "vlm":
        n += cfg.n_vis_tokens
    return n


def _assemble_input(cfg, params, batch, rules=None):
    """Token embeddings + any learned/stub prefixes. Returns (x, positions)."""
    x = _embed_tokens(cfg, params, batch["tokens"], rules=rules)
    B = x.shape[0]
    prefix = []
    if cfg.n_meta_tokens:
        prefix.append(jnp.broadcast_to(params["meta"],
                                       (B,) + params["meta"].shape))
    if cfg.family == "vlm":
        vis = batch["vis_embeds"].astype(x.dtype) @ params["vis_proj"]
        prefix.append(vis)
    if prefix:
        x = jnp.concatenate(prefix + [x], axis=1)
    positions = jnp.arange(x.shape[1])
    return x, positions


def lm_apply(cfg: ModelConfig, params, batch, rules=None):
    """Teacher-forcing forward. Returns (logits over token positions, aux)."""
    x, positions = _assemble_input(cfg, params, batch, rules=rules)
    x, aux = tfm.apply_stack_train(cfg, params["stack"], x, positions,
                                   rules=rules)
    x = apply_norm(cfg, params["ln_f"], x)
    npre = _prefix_len(cfg)
    if npre:
        x = x[:, npre:]
    if cfg.family == "audio":
        logits = jnp.einsum("bsd,cdv->bscv", x, params["head"])
    else:
        logits = x @ params["head"]
    logits = softcap(logits.astype(jnp.float32), cfg.final_softcap)
    return logits, aux


def _xent(logits, targets):
    """Mean token cross-entropy in f32. logits (..., V), targets (...)."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    return jnp.mean(logz - _gold_logit(logits, targets))


def _gold_logit(logits, targets):
    """logits[..., targets] as a one-hot contraction (exact: one nonzero
    term). A take_along_axis gather over a vocab-sharded logits tensor
    with a data-sharded batch aborts XLA's SPMD partitioner inside the
    mesh-native partial-manual train step."""
    return jnp.sum(logits * jax.nn.one_hot(targets, logits.shape[-1],
                                           dtype=logits.dtype), axis=-1)


_XENT_CHUNK = 512


def _head_and_xent(cfg, params, x, targets):
    """Final projection + cross-entropy, chunked over the sequence.

    The unchunked path materializes (B, S, V) f32 logits plus their
    gradient — ~12 GB/device for the 256k-vocab archs at 4k training
    (measured in the dry-run). Chunking the head matmul + xent over
    S/512 slices under jax.checkpoint bounds it at (B, 512, V_shard).
    Returns (loss_mean, acc_mean).
    """
    B, S = targets.shape[0], targets.shape[1]

    def head_logits(xb):
        if cfg.family == "audio":
            lg = jnp.einsum("bsd,cdv->bscv", xb, params["head"])
        else:
            lg = xb @ params["head"]
        return softcap(lg.astype(jnp.float32), cfg.final_softcap)

    if S % _XENT_CHUNK or S <= _XENT_CHUNK:
        logits = head_logits(x)
        acc = jnp.mean((jnp.argmax(logits, -1) == targets)
                       .astype(jnp.float32))
        return _xent(logits, targets), acc

    n_chunks = S // _XENT_CHUNK
    xc = jnp.moveaxis(x.reshape(B, n_chunks, _XENT_CHUNK, -1), 1, 0)
    tc = jnp.moveaxis(targets.reshape((B, n_chunks, _XENT_CHUNK)
                                      + targets.shape[2:]), 1, 0)

    @jax.checkpoint
    def chunk_fn(carry, xt):
        xb, tb = xt
        logits = head_logits(xb)
        logz = jax.nn.logsumexp(logits, axis=-1)
        loss_sum = jnp.sum(logz - _gold_logit(logits, tb))
        acc_sum = jnp.sum((jnp.argmax(logits, -1) == tb).astype(jnp.float32))
        return (carry[0] + loss_sum, carry[1] + acc_sum), None

    (loss_sum, acc_sum), _ = jax.lax.scan(
        chunk_fn, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (xc, tc))
    n_tok = np.prod(targets.shape)
    return loss_sum / n_tok, acc_sum / n_tok


def lm_loss(cfg: ModelConfig, params, batch, rules=None):
    x, positions = _assemble_input(cfg, params, batch, rules=rules)
    x, aux = tfm.apply_stack_train(cfg, params["stack"], x, positions,
                                   rules=rules)
    x = apply_norm(cfg, params["ln_f"], x)
    npre = _prefix_len(cfg)
    if npre:
        x = x[:, npre:]
    loss, acc = _head_and_xent(cfg, params, x, batch["targets"])
    total = loss + cfg.router_aux_coef * aux
    return total, {"loss": loss, "aux": aux, "acc": acc}


def lm_init_cache(cfg: ModelConfig, batch_size: int, seq_len: int, dtype=None):
    dtype = dtype or _dtype(cfg)
    total = seq_len + _prefix_len(cfg)
    caches, dims = tfm.init_stack_cache(cfg, batch_size, total, dtype)
    return {"layers": caches, "pos": jnp.zeros((), jnp.int32)}, \
           {"layers": dims, "pos": ()}


def lm_prefill(cfg: ModelConfig, params, cache, batch, rules=None):
    """Batched prefill: full forward + cache population.

    Returns (last-token logits, cache positioned after the prompt).
    """
    x, positions = _assemble_input(cfg, params, batch, rules=rules)
    x, new_layers = tfm.apply_stack_prefill(cfg, params["stack"],
                                            cache["layers"], x, positions,
                                            rules=rules)
    x = apply_norm(cfg, params["ln_f"], x)[:, -1]
    if cfg.family == "audio":
        logits = jnp.einsum("bd,cdv->bcv", x, params["head"])
    else:
        logits = x @ params["head"]
    logits = softcap(logits.astype(jnp.float32), cfg.final_softcap)
    total = positions.shape[0]
    return logits, {"layers": new_layers,
                    "pos": jnp.asarray(total, jnp.int32)}


def lm_decode_step(cfg: ModelConfig, params, cache, tokens, rules=None):
    """One-token decode. tokens: (B,) int32 (or (B, n_codebooks) for audio).

    Returns (logits (B, V) or (B, CB, V), new_cache).
    """
    tok = tokens[:, None] if cfg.family != "audio" else tokens[:, None, :]
    x = _embed_tokens(cfg, params, tok, rules=rules)   # (B, 1, D)
    pos = cache["pos"]
    x, new_layers = tfm.apply_stack_decode(cfg, params["stack"],
                                           cache["layers"], x, pos, rules=rules)
    x = apply_norm(cfg, params["ln_f"], x)[:, 0]
    if cfg.family == "audio":
        logits = jnp.einsum("bd,cdv->bcv", x, params["head"])
    else:
        logits = x @ params["head"]
    logits = softcap(logits.astype(jnp.float32), cfg.final_softcap)
    return logits, {"layers": new_layers, "pos": pos + 1}


# ------------------------------------------------------------------
# paged serving path (see docs/ARCHITECTURE.md §8)
# ------------------------------------------------------------------


def lm_init_paged_cache(cfg: ModelConfig, max_batch: int, n_pages: int,
                        page_size: int, dtype=None):
    """Serving caches: per-spec page pools + stacked recurrent states."""
    dtype = dtype or _dtype(cfg)
    return tfm.init_stack_paged_cache(cfg, max_batch, n_pages, page_size,
                                      dtype)


def lm_paged_decode_step(cfg: ModelConfig, params, caches, tokens, pos_b,
                         tables, page_size: int):
    """One fixed-shape continuous-batching token step.

    tokens: (B,) int32 ((B, CB) for audio); pos_b: (B,) per-sequence
    positions (tokens already cached — inactive slots carry pos 0 and
    write the trash page); tables: (B, TW) block tables. Returns
    (logits (B, V) or (B, CB, V), new_caches).
    """
    tok = tokens[:, None] if cfg.family != "audio" else tokens[:, None, :]
    x = _embed_tokens(cfg, params, tok)                # (B, 1, D)
    x, new_caches = tfm.apply_stack_decode_paged(
        cfg, params["stack"], caches, x, pos_b, tables, page_size)
    x = apply_norm(cfg, params["ln_f"], x)[:, 0]
    if cfg.family == "audio":
        logits = jnp.einsum("bd,cdv->bcv", x, params["head"])
    else:
        logits = x @ params["head"]
    logits = softcap(logits.astype(jnp.float32), cfg.final_softcap)
    return logits, new_caches


def lm_paged_prefill_chunk(cfg: ModelConfig, params, caches, batch, n_valid,
                           slot, tables, page_size: int):
    """Prefill ONE batch slot's prompt chunk into its pages.

    batch: single-sequence batch dict (tokens (1, S_pad), + vis_embeds)
    padded to the engine's static chunk length; n_valid: real token
    count INCLUDING the meta/vis prefix; slot: batch-slot index (traced
    ok). Exact for attention-only stacks at any n_valid (pad K/V goes to
    the trash page, causal masking hides pad queries); recurrent stacks
    additionally require n_valid == S_total — the engine routes those
    through :func:`lm_paged_prefix_fill` + step-prefill instead.
    Returns (next-token logits (1, V)/(1, CB, V), new_caches).
    """
    x, _ = _assemble_input(cfg, params, batch)         # (1, S_total, D)
    table_row = jnp.take(tables, slot, axis=0)         # (TW,)
    x, new_caches = tfm.apply_stack_prefill_paged(
        cfg, params["stack"], caches, x, n_valid, slot, table_row, page_size)
    x = apply_norm(cfg, params["ln_f"], x)
    x_last = jax.lax.dynamic_slice_in_dim(x, n_valid - 1, 1, axis=1)[:, 0]
    if cfg.family == "audio":
        logits = jnp.einsum("bd,cdv->bcv", x_last, params["head"])
    else:
        logits = x_last @ params["head"]
    logits = softcap(logits.astype(jnp.float32), cfg.final_softcap)
    return logits, new_caches


def lm_paged_prefix_fill(cfg: ModelConfig, params, caches, slot, tables,
                         page_size: int, vis_embeds=None):
    """Run the learned/stub prefix (meta tokens, vis embeds) for one slot
    — static exact length, so recurrent states stay bit-exact. The
    engine then feeds the prompt itself through the decode step
    (step-prefill). No-op (error) when the model has no prefix."""
    npre = _prefix_len(cfg)
    assert npre > 0, "prefix fill on a model without a prefix"
    parts = []
    if cfg.n_meta_tokens:
        parts.append(jnp.broadcast_to(params["meta"],
                                      (1,) + params["meta"].shape))
    if cfg.family == "vlm":
        parts.append(vis_embeds.astype(_dtype(cfg)) @ params["vis_proj"])
    x = jnp.concatenate(parts, axis=1) if len(parts) > 1 else parts[0]
    table_row = jnp.take(tables, slot, axis=0)
    _, new_caches = tfm.apply_stack_prefill_paged(
        cfg, params["stack"], caches, x, jnp.asarray(npre, jnp.int32), slot,
        table_row, page_size)
    return new_caches


@dataclasses.dataclass
class LM:
    cfg: ModelConfig

    def init(self, key):
        return init_lm(self.cfg, key)[0]

    def abstract(self):
        """(param ShapeDtypeStructs, logical dims) without allocation."""
        captured = {}

        def f(key):
            params, dims = init_lm(self.cfg, key)
            captured["dims"] = dims
            return params

        shapes = jax.eval_shape(f, jax.random.key(0))
        return shapes, captured["dims"]

    def apply(self, params, batch, rules=None):
        return lm_apply(self.cfg, params, batch, rules=rules)

    def loss(self, params, batch, rules=None):
        return lm_loss(self.cfg, params, batch, rules=rules)

    def init_cache(self, batch_size, seq_len, dtype=None):
        return lm_init_cache(self.cfg, batch_size, seq_len, dtype)

    def cache_abstract(self, batch_size, seq_len, dtype=None):
        captured = {}

        def f():
            cache, dims = lm_init_cache(self.cfg, batch_size, seq_len, dtype)
            captured["dims"] = dims
            return cache

        shapes = jax.eval_shape(f)
        return shapes, captured["dims"]

    def prefill(self, params, cache, batch, rules=None):
        return lm_prefill(self.cfg, params, cache, batch, rules=rules)

    def decode_step(self, params, cache, tokens, rules=None):
        return lm_decode_step(self.cfg, params, cache, tokens, rules=rules)

    # -- paged serving path --------------------------------------------

    def init_paged_cache(self, max_batch, n_pages, page_size, dtype=None):
        return lm_init_paged_cache(self.cfg, max_batch, n_pages, page_size,
                                   dtype)

    def paged_decode_step(self, params, caches, tokens, pos_b, tables,
                          page_size):
        return lm_paged_decode_step(self.cfg, params, caches, tokens, pos_b,
                                    tables, page_size)

    def paged_prefill_chunk(self, params, caches, batch, n_valid, slot,
                            tables, page_size):
        return lm_paged_prefill_chunk(self.cfg, params, caches, batch,
                                      n_valid, slot, tables, page_size)

    def paged_prefix_fill(self, params, caches, slot, tables, page_size,
                          vis_embeds=None):
        return lm_paged_prefix_fill(self.cfg, params, caches, slot, tables,
                                    page_size, vis_embeds=vis_embeds)


def build_model(cfg: ModelConfig) -> LM:
    if cfg.family == "convnet":
        raise ValueError("use repro.models.convnet directly for convnets")
    return LM(cfg)
