"""Pallas flash-attention backward: the two recompute sweeps.

Recompute-based backward (Dao et al., arXiv 2205.14135; Rabe & Staats,
arXiv 2112.05682): nothing O(S·T) is stashed — the forward saves only
(O, lse) per row, and each sweep rebuilds the block scores
S = (Q·Kᵀ)·dscale it needs, recovering the probabilities as
p = exp(softcap(S) − lse) and the score gradient as

    Δ_i  = Σ_d dO_i · O_i                       (one XLA reduction)
    dS   = p ⊙ (dO·Vᵀ − Δ) ⊙ softcap'(S) · dscale

Two launches (ARCHITECTURE.md §7 has the tiling diagram):

  dq sweep    grid (B, Hkv, nq, nk), k innermost ("arbitrary") — the
              dq accumulators of one q block's G query heads live in
              VMEM across the k sweep: dq_ig = Σ_j dS_ijg · K_j, one K/V
              block fetched per step for the whole group.
  dk/dv sweep grid (B, Hkv, nk, nq), q innermost — dk/dv accumulators
              for one KV block live in VMEM across the q sweep, and the
              G query heads of the group accumulate into their shared
              kv head inside the block:
              dv_j = Σ_i Σ_g p_ijgᵀ·dO_ig, dk_j = Σ_i Σ_g dS_ijgᵀ·Q_ig.

In both, q/dO arrive as (block_q, G·D) slabs, head g a static lane
slice. Both sweeps reuse the forward's block-skip predicate, so causal /
sliding-window bands skip dead blocks entirely, and clamp the index map
of the swept operand onto the live band, so a dead step copies nothing.
Fully-masked rows carry lse == NEG_INF and zero dO·O, so every gradient
contribution is re-masked to exactly zero (no NaN from the −1e30 fill).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_attention import (NEG_INF, band_mask, block_live,
                                            heads_flat, kv_block_index,
                                            q_block_index, row_to_col)


def _block_p_ds(q, kb, vb, do, lse, delta, q_start, k_start, *,
                block_q: int, block_k: int, causal: bool,
                window: int | None, logit_softcap: float, dscale: float):
    """Recompute one (block_q, block_k) tile's p and dS from f32 operands.

    lse/delta are (block_q, 1) columns. A fully-masked row carries
    lse == NEG_INF; exp(s - NEG_INF) would overflow, so the row's lse is
    swapped for 0 first — its p entries are all re-masked to 0 anyway.
    """
    s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * dscale
    if logit_softcap:
        s = logit_softcap * jnp.tanh(s / logit_softcap)
    mask = band_mask(q_start, k_start, block_q, block_k, causal, window)
    lse_safe = jnp.where(lse > 0.5 * NEG_INF, lse, 0.0)
    p = jnp.where(mask, jnp.exp(s - lse_safe), 0.0)
    dp = jax.lax.dot_general(do, vb, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta)
    if logit_softcap:
        # d/dx [c·tanh(x/c)] = 1 − tanh²(x/c); s here is already the
        # capped value c·tanh(x/c), so tanh(x/c) = s/c without recompute
        ds = ds * (1.0 - jnp.square(s / logit_softcap))
    return p, ds * dscale


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               acc_scr, lse_scr, delta_scr, *, block_q: int, block_k: int,
               group: int, causal: bool, window: int | None,
               logit_softcap: float, dscale: float):
    i = pl.program_id(2)               # q block (parallel)
    j = pl.program_id(3)               # k block (innermost, sequential)
    nk = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)
        # the q block's lse/Δ rows as columns, once for the whole k sweep
        for g in range(group):
            lse_scr[g] = row_to_col(lse_ref[0, g])         # (bq, 1)
            delta_scr[g] = row_to_col(delta_ref[0, g])

    q_start = i * block_q
    k_start = j * block_k

    @pl.when(block_live(q_start, k_start, block_q, block_k, causal, window))
    def _compute():
        kb = k_ref[0].astype(jnp.float32)                  # (bk, d)
        vb = v_ref[0].astype(jnp.float32)
        d = kb.shape[1]
        # the G query heads sharing this kv head, as in _dkv_kernel
        for g in range(group):
            hd = slice(g * d, (g + 1) * d)
            q = q_ref[0, :, hd].astype(jnp.float32)        # (bq, d)
            do = do_ref[0, :, hd].astype(jnp.float32)
            _, ds = _block_p_ds(
                q, kb, vb, do, lse_scr[g], delta_scr[g], q_start, k_start,
                block_q=block_q, block_k=block_k, causal=causal,
                window=window, logit_softcap=logit_softcap, dscale=dscale)
            acc_scr[:, hd] += jax.lax.dot_general(
                ds, kb, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    @pl.when(j == nk - 1)
    def _finalize():
        dq_ref[0] = acc_scr[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *, block_q: int,
                block_k: int, group: int, causal: bool, window: int | None,
                logit_softcap: float, dscale: float):
    j = pl.program_id(2)               # k block (parallel)
    i = pl.program_id(3)               # q block (innermost, sequential)
    nq = pl.num_programs(3)

    @pl.when(i == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    q_start = i * block_q
    k_start = j * block_k

    @pl.when(block_live(q_start, k_start, block_q, block_k, causal, window))
    def _compute():
        kb = k_ref[0].astype(jnp.float32)                  # (bk, d)
        vb = v_ref[0].astype(jnp.float32)
        d = kb.shape[1]
        # GQA head-group accumulation: the G query heads sharing this kv
        # head each contribute a (bq, bk) tile into the SAME dk/dv block;
        # head g is lane slice [g·d, (g+1)·d) of the (bq, G·d) q/dO block
        for g in range(group):
            q = q_ref[0, :, g * d:(g + 1) * d].astype(jnp.float32)
            do = do_ref[0, :, g * d:(g + 1) * d].astype(jnp.float32)
            lse = row_to_col(lse_ref[0, g])                # (bq, 1)
            delta = row_to_col(delta_ref[0, g])
            p, ds = _block_p_ds(
                q, kb, vb, do, lse, delta, q_start, k_start,
                block_q=block_q, block_k=block_k, causal=causal,
                window=window, logit_softcap=logit_softcap, dscale=dscale)
            dv_scr[...] += jax.lax.dot_general(
                p, do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dk_scr[...] += jax.lax.dot_general(
                ds, q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    @pl.when(i == nq - 1)
    def _finalize():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def flash_attention_bwd_pallas(q, k, v, out, lse, dout, *, causal: bool,
                               window: int | None, logit_softcap: float,
                               block_q: int, block_k: int, dscale: float,
                               interpret: bool = True):
    """(dq, dk, dv) via the two recompute sweeps. Shapes as the forward;
    lse is the forward's (B, Hq, 1, S) f32 residual."""
    B, S, Hq, D = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    nq, nk = S // block_q, T // block_k

    # Δ_i = Σ_d dO·O per row — elementwise, stays in XLA (not a launch)
    delta = jnp.einsum("bshd,bshd->bhs", dout.astype(jnp.float32),
                       out.astype(jnp.float32))[:, :, None, :]
    qf, kf, vf, dof = (heads_flat(x) for x in (q, k, v, dout))

    band = dict(block_q=block_q, block_k=block_k, causal=causal,
                window=window)
    common = dict(group=G, logit_softcap=logit_softcap, dscale=dscale,
                  **band)

    # q/dO/lse/Δ arrive as whole GQA groups in both sweeps: a G·D lane
    # block (or G rows of lse/Δ) at head-block index h covers query heads
    # [h·G, (h+1)·G), the kv head h's group. Dead steps repeat a live
    # step's block index (kv_block_index, q_block_index): no copy.
    kv = lambda b, h, i, j: (b, kv_block_index(i, j, nk=nk, **band), h)
    slab = lambda b, h, i, j: (b, i, h)
    rows = lambda b, h, i, j: (b, h, 0, i)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **common),
        grid=(B, Hkv, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, G * D), slab),
            pl.BlockSpec((1, block_k, D), kv),
            pl.BlockSpec((1, block_k, D), kv),
            pl.BlockSpec((1, block_q, G * D), slab),
            pl.BlockSpec((1, G, 1, block_q), rows),
            pl.BlockSpec((1, G, 1, block_q), rows),
        ],
        out_specs=pl.BlockSpec((1, block_q, G * D), slab),
        out_shape=jax.ShapeDtypeStruct((B, S, Hq * D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, G * D), jnp.float32),
            pltpu.VMEM((G, block_q, 1), jnp.float32),
            pltpu.VMEM((G, block_q, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf, dof, lse, delta)

    qi = lambda j, i: q_block_index(j, i, nq=nq, **band)
    slab_t = lambda b, h, j, i: (b, qi(j, i), h)
    rows_t = lambda b, h, j, i: (b, h, 0, qi(j, i))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, **common),
        grid=(B, Hkv, nk, nq),
        in_specs=[
            pl.BlockSpec((1, block_q, G * D), slab_t),
            pl.BlockSpec((1, block_k, D), lambda b, h, j, i: (b, j, h)),
            pl.BlockSpec((1, block_k, D), lambda b, h, j, i: (b, j, h)),
            pl.BlockSpec((1, block_q, G * D), slab_t),
            pl.BlockSpec((1, G, 1, block_q), rows_t),
            pl.BlockSpec((1, G, 1, block_q), rows_t),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, D), lambda b, h, j, i: (b, j, h)),
            pl.BlockSpec((1, block_k, D), lambda b, h, j, i: (b, j, h)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, T, Hkv * D), k.dtype),
            jax.ShapeDtypeStruct((B, T, Hkv * D), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf, dof, lse, delta)

    return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape))
