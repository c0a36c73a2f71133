"""Pallas flash-attention kernels (TPU target; numerics checked in interpret
mode, Mosaic compile checked for v5e in tests/test_tpu_compile.py).

Causal GQA attention with optional sliding window and logit softcap —
the framework's perf-critical compute layer for training/prefill
(the decode step is matmul-thin and stays in XLA; see
``repro.models.attention.run_attention``).

Forward tiling (ARCHITECTURE.md §7): grid = (B, Hq, nq, nk) with the key
axis innermost ("arbitrary" semantics → sequential), so the
online-softmax accumulators (m, l, acc) live in VMEM scratch across the
nk sweep. Block shapes are (block_q, head_dim) / (block_k, head_dim)
with head_dim padded to 128 by ``ops.py`` — MXU-aligned. Causality and
the sliding window are enforced both by *block skipping* (pl.when —
skipped blocks cost no MXU work, the banded-compute trick) and an
in-block position mask. Alongside O the forward emits the per-row
logsumexp — the residual the recompute-based backward
(``flash_attention_bwd``) rebuilds block scores from, instead of
stashing the O(S·T) probability tensor.

The whole fwd+bwd pipeline sits under one ``jax.custom_vjp``
(:func:`flash_attention_pallas`), so ``jax.grad`` through the Pallas op
costs exactly 1 forward + 2 backward launches.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def heads_flat(x):
    """(B, S, H, D) -> (B, S, H·D), a free reshape. The kernels block one
    head as a (rows, D) tile of the flattened lane axis, so the last two
    block dims (rows, D) are tile-legal for Mosaic — a (…, 1, D) block
    over the head axis is not (second-minor dim 1 of H)."""
    B, S, H, D = x.shape
    return x.reshape(B, S, H * D)


def _eye(n: int):
    return (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
            == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))


def col_to_row(col):
    """(n, 1) -> (1, n) without a relayout: mask the broadcast diagonal
    and reduce over sublanes (exact — one nonzero term per column)."""
    return jnp.sum(jnp.where(_eye(col.shape[0]), col, 0.0), axis=0,
                   keepdims=True)


def row_to_col(row):
    """(1, n) -> (n, 1); the inverse of :func:`col_to_row`."""
    return jnp.sum(jnp.where(_eye(row.shape[1]), row, 0.0), axis=1,
                   keepdims=True)


def block_live(q_start, k_start, block_q: int, block_k: int, causal: bool,
               window: int | None):
    """Block-level skip predicate shared by the forward and both backward
    sweeps: a (q-block, k-block) pair is dead when the causal triangle or
    the sliding-window band excludes every (row, col) position in it."""
    live = True
    if causal:
        live = k_start <= q_start + block_q - 1
    if window is not None:
        live = jnp.logical_and(
            live, k_start + block_k - 1 >= q_start - (window - 1))
    return live


def band_mask(q_start, k_start, block_q: int, block_k: int, causal: bool,
              window: int | None):
    """In-block (block_q, block_k) boolean mask for the causal/window band."""
    q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                               (block_q, block_k), 0)
    k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                               (block_q, block_k), 1)
    mask = k_pos <= q_pos if causal else k_pos >= 0
    if window is not None:
        mask = jnp.logical_and(mask, q_pos - k_pos < window)
    return mask


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                  *, block_q: int, block_k: int, causal: bool,
                  window: int | None, logit_softcap: float, dscale: float):
    i = pl.program_id(2)               # q block
    j = pl.program_id(3)               # k block
    nk = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = i * block_q
    k_start = j * block_k

    @pl.when(block_live(q_start, k_start, block_q, block_k, causal, window))
    def _compute():
        q = q_ref[0].astype(jnp.float32)                   # (bq, d)
        k = k_ref[0].astype(jnp.float32)                   # (bk, d)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * dscale
        if logit_softcap:
            s = logit_softcap * jnp.tanh(s / logit_softcap)
        mask = band_mask(q_start, k_start, block_q, block_k, causal, window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_cur = jnp.max(s, axis=1)[:, None]                # (bq, 1)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        # A fully-masked row has m_new == NEG_INF, so s - m_new == 0 and
        # the bare exp would claim p == 1 per masked entry (a bogus
        # uniform mean of v). Re-masking p keeps l at 0 there, which
        # _finalize turns into a zero output row and an lse of NEG_INF.
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        l_new = alpha * l_prev + jnp.sum(p, axis=1)[:, None]
        acc_scr[...] = alpha * acc_scr[...] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(j == nk - 1)
    def _finalize():
        l = l_scr[...]
        safe = jnp.where(l > 0.0, l, 1.0)
        o_ref[0] = (acc_scr[...] / safe).astype(o_ref.dtype)
        lse = jnp.where(l > 0.0, m_scr[...] + jnp.log(safe), NEG_INF)
        lse_ref[0, 0] = col_to_row(lse)


def _flash_forward(q, k, v, causal, window, logit_softcap, block_q, block_k,
                   dscale, interpret):
    """Raw forward launch. Returns (out (B,S,Hq,D) q.dtype,
    lse (B,Hq,1,S) f32) — lse is the backward's recompute residual."""
    B, S, Hq, D = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    grid = (B, Hq, S // block_q, T // block_k)

    kernel = functools.partial(
        _flash_kernel, block_q=block_q, block_k=block_k, causal=causal,
        window=window, logit_softcap=logit_softcap, dscale=dscale)

    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, h, i, j: (b, i, h)),
            pl.BlockSpec((1, block_k, D), lambda b, h, i, j: (b, j, h // G)),
            pl.BlockSpec((1, block_k, D), lambda b, h, i, j: (b, j, h // G)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, h, i, j: (b, i, h)),
            # per-row residuals (lse, Δ) are stored lane-dense as
            # (B, Hq, 1, S): the last two block dims (1, block_q) are legal
            pl.BlockSpec((1, 1, 1, block_q), lambda b, h, i, j: (b, h, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, Hq * D), q.dtype),
            jax.ShapeDtypeStruct((B, Hq, 1, S), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(heads_flat(q), heads_flat(k), heads_flat(v))
    return out.reshape(B, S, Hq, D), lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash(q, k, v, causal, window, logit_softcap, block_q, block_k, dscale,
           interpret):
    out, _ = _flash_forward(q, k, v, causal, window, logit_softcap,
                            block_q, block_k, dscale, interpret)
    return out


def _flash_fwd(q, k, v, causal, window, logit_softcap, block_q, block_k,
               dscale, interpret):
    out, lse = _flash_forward(q, k, v, causal, window, logit_softcap,
                              block_q, block_k, dscale, interpret)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, window, logit_softcap, block_q, block_k, dscale,
               interpret, res, dout):
    # local import: flash_attention_bwd imports NEG_INF/mask helpers from
    # this module, so the dependency must stay one-way at import time
    from repro.kernels.flash_attention_bwd import flash_attention_bwd_pallas
    q, k, v, out, lse = res
    return flash_attention_bwd_pallas(
        q, k, v, out, lse, dout, causal=causal, window=window,
        logit_softcap=logit_softcap, block_q=block_q, block_k=block_k,
        dscale=dscale, interpret=interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention_pallas(q, k, v, *, causal: bool = True,
                           window: int | None = None,
                           logit_softcap: float = 0.0,
                           block_q: int = 128, block_k: int = 128,
                           sm_scale: float | None = None,
                           interpret: bool = True):
    """q: (B, S, Hq, D); k/v: (B, T, Hkv, D); Hq = G·Hkv. D % 128 == 0
    (ops.py pads; pass sm_scale=1/sqrt(unpadded_D)). Returns (B,S,Hq,D).

    Differentiable: ``jax.grad`` hits the custom VJP — the backward
    recomputes block scores from the saved (q, k, v, O, lse) residuals
    and runs the two Pallas sweeps in ``flash_attention_bwd`` (dq with k
    innermost, then dk/dv with q innermost).
    """
    B, S, Hq, D = q.shape
    T = k.shape[1]
    block_q = min(block_q, S)
    block_k = min(block_k, T)
    assert S % block_q == 0 and T % block_k == 0, (S, block_q, T, block_k)
    dscale = float(sm_scale) if sm_scale is not None else float(D) ** -0.5
    return _flash(q, k, v, causal, window, float(logit_softcap),
                  block_q, block_k, dscale, interpret)
