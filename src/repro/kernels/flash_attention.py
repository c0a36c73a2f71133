"""Pallas flash-attention kernels (TPU target; numerics checked in interpret
mode, Mosaic compile checked for v5e in tests/test_tpu_compile.py).

Causal GQA attention with optional sliding window and logit softcap —
the framework's perf-critical compute layer for training/prefill
(the decode step is matmul-thin and stays in XLA; see
``repro.models.attention.run_attention``).

Forward tiling (ARCHITECTURE.md §7): grid = (B, Hkv, nq, nk) with the
key axis innermost ("arbitrary" semantics → sequential), so the
online-softmax accumulators (m, l, acc) live in VMEM scratch across the
nk sweep. Each step takes one KV head and its whole GQA group of G query
heads: q and O arrive as (block_q, G·head_dim) slabs, K/V as one
(block_k, head_dim) block shared by the G heads, with head_dim padded to
128 by ``ops.py`` — MXU-aligned. The blocks follow from the shapes
(:func:`flash_blocks`). Causality and the sliding window are enforced
both by *block skipping* (pl.when — skipped blocks cost no MXU work, the
banded-compute trick) and an in-block position mask; the K/V index map
clamps a skipped step onto a live block, so it costs no copy either.
Alongside O the forward emits the per-row logsumexp — the residual the
recompute-based backward (``flash_attention_bwd``) rebuilds block scores
from, instead of stashing the O(S·T) probability tensor.

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
                  *, block_q: int, block_k: int, group: int, causal: bool,
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
        k = k_ref[0].astype(jnp.float32)                   # (bk, d)
        v = v_ref[0].astype(jnp.float32)
        d = k.shape[1]
        mask = band_mask(q_start, k_start, block_q, block_k, causal, window)
        # the G query heads sharing this kv head: head g is lane slice
        # [g·d, (g+1)·d) of the (bq, G·d) q/o slab, row g of m/l
        for g in range(group):
            hd = slice(g * d, (g + 1) * d)
            q = q_ref[0, :, hd].astype(jnp.float32)        # (bq, d)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = s * dscale
            if logit_softcap:
                s = logit_softcap * jnp.tanh(s / logit_softcap)
            s = jnp.where(mask, s, NEG_INF)

            m_prev = m_scr[g]
            l_prev = l_scr[g]
            m_cur = jnp.max(s, axis=1)[:, None]            # (bq, 1)
            m_new = jnp.maximum(m_prev, m_cur)
            alpha = jnp.exp(m_prev - m_new)
            # A fully-masked row has m_new == NEG_INF, so s - m_new == 0
            # and the bare exp would claim p == 1 per masked entry (a
            # bogus uniform mean of v). Re-masking p keeps l at 0 there,
            # which _finalize turns into a zero output row and an lse of
            # NEG_INF.
            p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
            l_new = alpha * l_prev + jnp.sum(p, axis=1)[:, None]
            acc_scr[:, hd] = alpha * acc_scr[:, hd] + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_scr[g] = m_new
            l_scr[g] = l_new

    @pl.when(j == nk - 1)
    def _finalize():
        d = acc_scr.shape[1] // group
        for g in range(group):
            hd = slice(g * d, (g + 1) * d)
            l = l_scr[g]
            safe = jnp.where(l > 0.0, l, 1.0)
            o_ref[0, :, hd] = (acc_scr[:, hd] / safe).astype(o_ref.dtype)
            lse = jnp.where(l > 0.0, m_scr[g] + jnp.log(safe), NEG_INF)
            lse_ref[0, g] = col_to_row(lse)


def kv_block_index(i, j, *, block_q: int, block_k: int, nk: int,
                   causal: bool, window: int | None):
    """k/v block the forward and dq sweeps fetch at step (q block i, k
    step j): j itself, clamped into the q block's live band. A dead step
    then repeats the index of a neighbouring live step, so the pipeline
    issues no copy for it (``block_live`` still gates the compute)."""
    if causal:
        j = jnp.minimum(j, (i * block_q + block_q - 1) // block_k)
    if window is not None:
        first = jnp.maximum(i * block_q - window + 1, 0) // block_k
        j = jnp.minimum(jnp.maximum(j, first), nk - 1)
    return j


def q_block_index(j, i, *, block_q: int, block_k: int, nq: int,
                  causal: bool, window: int | None):
    """q block the dk/dv sweep fetches at step (k block j, q step i): i
    clamped into the k block's live band, as :func:`kv_block_index`."""
    if window is not None:
        i = jnp.minimum(i, (j * block_k + block_k + window - 2) // block_q)
    if causal:
        i = jnp.minimum(jnp.maximum(i, (j * block_k) // block_q), nq - 1)
    return i


def _flash_forward(q, k, v, causal, window, logit_softcap, block_q, block_k,
                   dscale, interpret):
    """Raw forward launch. Returns (out (B,S,Hq,D) q.dtype,
    lse (B,Hq,1,S) f32) — lse is the backward's recompute residual."""
    B, S, Hq, D = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    grid = (B, Hkv, S // block_q, T // block_k)
    band = dict(block_q=block_q, block_k=block_k, causal=causal,
                window=window)
    kv = lambda b, h, i, j: (b, kv_block_index(i, j, nk=grid[3], **band), h)
    slab = lambda b, h, i, j: (b, i, h)

    kernel = functools.partial(
        _flash_kernel, group=G, logit_softcap=logit_softcap, dscale=dscale,
        **band)

    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            # one KV head's whole GQA group per step: the (bq, G·D) q slab
            # at head block h holds query heads [h·G, (h+1)·G)
            pl.BlockSpec((1, block_q, G * D), slab),
            pl.BlockSpec((1, block_k, D), kv),
            pl.BlockSpec((1, block_k, D), kv),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, G * D), slab),
            # per-row residuals (lse, Δ) are stored lane-dense as
            # (B, Hq, 1, S): the last two block dims (1, block_q) are legal
            pl.BlockSpec((1, G, 1, block_q), lambda b, h, i, j: (b, h, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, Hq * D), q.dtype),
            jax.ShapeDtypeStruct((B, Hq, 1, S), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((G, block_q, 1), jnp.float32),
            pltpu.VMEM((G, block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, G * D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(heads_flat(q), heads_flat(k), heads_flat(v))
    return out.reshape(B, S, Hq, D), lse


#: block sizes the shape rule picks from; 128 is the lane width
BLOCKS = (1024, 512, 256, 128)
#: ceiling on :func:`flash_vmem_bytes`: v5e's 16 MiB scoped VMEM less
#: room for the kernel bodies' smaller temporaries (test_tpu_compile.py
#: compiles the choices for G ∈ {1, 4, 8} and D ∈ {128, 256})
VMEM_BUDGET = 14 << 20


def flash_vmem_bytes(block_q: int, block_k: int, D: int, G: int) -> int:
    """VMEM the largest of the three kernels holds at these blocks: the
    double-buffered in/out blocks (f32, the widest input dtype), the
    scratch, and f32 (block_q, block_k) score tiles, one per query head
    of the group and one more. Row vectors pad to 8 sublanes, column
    scratch to 128 lanes. Fitted to what Mosaic allocates for v5e: of
    256 compiles (G ∈ {1, 2, 4, 8}, D ∈ {128, 256}, every pair of blocks,
    with and without softcap) none that this puts under the budget ran
    out of scoped VMEM."""
    slab, kv = block_q * G * D * 4, block_k * D * 4
    rows = G * 8 * block_q * 4                     # one lse or Δ block
    cols = G * block_q * 128 * 4                   # one (G, bq, 1) scratch
    tiles = (G + 1) * block_q * block_k * 4
    fwd = 2 * (2 * slab + 2 * kv + rows) + 2 * cols + slab
    dq = 2 * (3 * slab + 2 * kv + 2 * rows) + 2 * cols + slab
    dkv = 2 * (2 * slab + 4 * kv + 2 * rows) + 2 * kv
    return max(fwd, dq, dkv) + tiles


def _seq_blocks(n: int) -> tuple[int, ...]:
    """Blocks that tile a length of n once padded as the wrapper pads it
    (to a multiple of 128; a length up to 128 is one block)."""
    if n <= 128:
        return (n,)
    padded = -(-n // 128) * 128
    return tuple(b for b in BLOCKS if padded % b == 0)


def flash_blocks(S: int, T: int, D: int, G: int,
                 window: int | None = None) -> tuple[int, int]:
    """(block_q, block_k) for query length S, key length T, (padded)
    head_dim D and GQA group G: the largest pair that tiles both lengths
    at their 128-padding, keeps block_k within the window rounded up to
    128, and fits :data:`VMEM_BUDGET` (else the smallest such pair). Ties
    go to the squarer pair, then to the wider block_k (the forward's k
    sweep is its inner loop)."""
    qs = _seq_blocks(S)
    ks = _seq_blocks(T)
    if window is not None:
        ks = tuple(b for b in ks if b <= -(-window // 128) * 128)
    pairs = sorted(((bq, bk) for bq in qs for bk in ks),
                   key=lambda p: (p[0] * p[1], min(p), p[1]), reverse=True)
    for bq, bk in pairs:
        if flash_vmem_bytes(bq, bk, D, G) <= VMEM_BUDGET:
            return bq, bk
    return pairs[-1]


def resolve_blocks(block_q, block_k, S, T, D, G, window):
    """A caller's blocks, capped at the lengths; :func:`flash_blocks`'
    choice for each left as None."""
    auto_q, auto_k = flash_blocks(S, T, D, G, window)
    return (auto_q if block_q is None else min(block_q, S),
            auto_k if block_k is None else min(block_k, T))


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
                           block_q: int | None = None,
                           block_k: int | None = None,
                           sm_scale: float | None = None,
                           interpret: bool = True):
    """q: (B, S, Hq, D); k/v: (B, T, Hkv, D); Hq = G·Hkv. D % 128 == 0
    (ops.py pads; pass sm_scale=1/sqrt(unpadded_D)). Returns (B,S,Hq,D).

    Differentiable: ``jax.grad`` hits the custom VJP — the backward
    recomputes block scores from the saved (q, k, v, O, lse) residuals
    and runs the two Pallas sweeps in ``flash_attention_bwd`` (dq with k
    innermost, then dk/dv with q innermost). Blocks left as None follow
    from the shapes (:func:`flash_blocks`).
    """
    B, S, Hq, D = q.shape
    T = k.shape[1]
    block_q, block_k = resolve_blocks(block_q, block_k, S, T, D,
                                      Hq // k.shape[2], window)
    assert S % block_q == 0 and T % block_k == 0, (S, block_q, T, block_k)
    dscale = float(sm_scale) if sm_scale is not None else float(D) ** -0.5
    return _flash(q, k, v, causal, window, float(logit_softcap),
                  block_q, block_k, dscale, interpret)
