"""Pallas paged-attention decode kernel (TPU target; numerics checked in
interpret mode, Mosaic compile checked for v5e) + the jnp gather
reference.

The serving tier stores K/V in a page pool ``(n_pages, page_size, Hkv,
D)`` addressed through per-sequence block tables ``(B, table_width)`` —
a logical ring at page granularity (``models.cache.paged_slot_pages``).
One decode step attends ONE query token per sequence against its live
pages:

- grid = (B, Hkv, TW) with the table-slot axis innermost ("arbitrary"
  semantics → sequential), so the online-softmax accumulators (m, l,
  acc) live in VMEM scratch across the page sweep — the same structure
  as ``flash_attention._flash_kernel`` with (q block → GQA group) and
  (k block → one K/V page).
- the block table and sequence lengths ride in as SCALAR-PREFETCH
  operands (``pltpu.PrefetchScalarGridSpec``): the K/V BlockSpec index
  maps read ``tables[b, j]`` to DMA the *physical* page for the
  sequence's j-th ring slot — the data-dependent gather that makes the
  cache paged.
- masking mirrors the flash kernels' band math at page granularity:
  a slot is dead when its ring position math yields a negative logical
  page or the whole page falls outside the sliding window; in-page
  positions are masked by recency (kpos <= q_pos) and window. A
  sequence with len 0 (inactive batch slot) produces an all-masked row
  → the flash-style safe division emits zeros, never NaN.

The jnp reference (:func:`paged_attention_ref`) performs the same
gather with ``jnp.take`` + ``naive_attention`` and is both the CPU hot
path (interpret-mode Pallas is emulation-slow) and the test oracle's
counterpart.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_attention import NEG_INF
from repro.models.attention import naive_attention
from repro.models.cache import paged_slot_pages


def paged_attention_ref(q, k_pages, v_pages, tables, lens, *, window=None,
                        logit_softcap=0.0):
    """Gather-based reference. q: (B, Hq, D) — the ONE current token per
    sequence (post-RoPE); k_pages/v_pages: (NP, ps, Hkv, D); tables:
    (B, TW) physical page per ring slot; lens: (B,) tokens written
    (query position = lens-1). Returns (B, Hq, D)."""
    B, Hq, D = q.shape
    ps = k_pages.shape[1]
    TW = tables.shape[1]
    cur_page = (lens - 1) // ps                       # (B,) floor: -1 if empty
    base = paged_slot_pages(TW, cur_page)             # (B, TW) logical pages
    k_pos = base[..., None] * ps + jnp.arange(ps)     # (B, TW, ps)
    k_pos = jnp.where(base[..., None] >= 0, k_pos, -1)
    k_pos = jnp.where(k_pos <= (lens - 1)[:, None, None], k_pos, -1)
    k = jnp.take(k_pages, tables, axis=0)             # (B, TW, ps, Hkv, D)
    v = jnp.take(v_pages, tables, axis=0)
    Hkv = k.shape[3]
    k = k.reshape(B, TW * ps, Hkv, D)
    v = v.reshape(B, TW * ps, Hkv, D)
    q_pos = (lens - 1)[:, None]                       # (B, 1)
    out = naive_attention(q[:, None], k, v, q_pos, k_pos.reshape(B, TW * ps),
                          window=window, logit_softcap=logit_softcap)
    return out[:, 0]


def _paged_kernel(tbl_ref, lens_ref, q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, page_size: int,
                  table_width: int, window: int | None,
                  logit_softcap: float, dscale: float):
    b = pl.program_id(0)
    j = pl.program_id(2)                               # table (ring) slot
    nj = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    ln = lens_ref[b]
    q_pos = ln - 1
    # ring math at page granularity (cache_positions lifted to pages):
    # slot j holds the largest logical page m' <= cur with m' % TW == j
    cur = jax.lax.div(q_pos, page_size)
    rem = jax.lax.rem(cur, table_width)
    base = jnp.where(j <= rem, cur - rem + j, cur - rem + j - table_width)
    live = jnp.logical_and(ln > 0, base >= 0)
    if window is not None:
        # whole page below the band → skip (banded-compute trick)
        live = jnp.logical_and(
            live, base * page_size + page_size - 1 >= q_pos - (window - 1))

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)            # (G, D)
        k = k_ref[0].astype(jnp.float32)               # (ps, D)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * dscale
        if logit_softcap:
            s = logit_softcap * jnp.tanh(s / logit_softcap)
        kpos = base * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, page_size), 1)              # (1, ps)
        mask = kpos <= q_pos
        if window is not None:
            mask = jnp.logical_and(mask, q_pos - kpos < window)
        s = jnp.where(mask, s, NEG_INF)                # (G, ps) via broadcast

        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1)[:, None])
        alpha = jnp.exp(m_prev - m_new)
        # re-mask p: a fully-masked page has s - m_new == 0 rows whose
        # bare exp would claim weight 1 (same guard as the flash kernel)
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        l_scr[...] = alpha * l_prev + jnp.sum(p, axis=1)[:, None]
        acc_scr[...] = alpha * acc_scr[...] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(j == nj - 1)
    def _finalize():
        l = l_scr[...]
        safe = jnp.where(l > 0.0, l, 1.0)
        o_ref[0, 0] = (acc_scr[...] / safe).astype(o_ref.dtype)


def paged_attention_pallas(q, k_pages, v_pages, tables, lens, *,
                           window: int | None = None,
                           logit_softcap: float = 0.0,
                           interpret: bool = True):
    """Pallas launch. Same contract as :func:`paged_attention_ref`.

    Table entries must be valid pool indices (``TRASH_PAGE`` = 0 for
    ring slots not yet allocated — the lens/ring masking hides them, the
    index map just needs somewhere legal to DMA from). Pads head_dim to
    the 128-lane MXU width like ``ops.flash_attention``.
    """
    B, Hq, D = q.shape
    NP, ps, Hkv = k_pages.shape[:3]
    G = Hq // Hkv
    TW = tables.shape[1]
    dscale = 1.0 / (D ** 0.5)

    pad_d = (-D) % 128
    if pad_d:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_d)))
        padp = ((0, 0), (0, 0), (0, 0), (0, pad_d))
        k_pages = jnp.pad(k_pages, padp)
        v_pages = jnp.pad(v_pages, padp)
    Dp = D + pad_d

    qg = q.reshape(B, Hkv, G, Dp) if Hkv > 1 else q.reshape(B, 1, G, Dp)

    kernel = functools.partial(
        _paged_kernel, page_size=ps, table_width=TW, window=window,
        logit_softcap=logit_softcap, dscale=dscale)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, Hkv, TW),
        in_specs=[
            pl.BlockSpec((1, 1, G, Dp),
                         lambda b, h, j, tbl, ln: (b, h, 0, 0)),
            # one kv head of one page: a (ps, Dp) lane block of the
            # (NP, ps, Hkv·Dp) pool view — tile-legal for Mosaic, where a
            # (…, 1, Dp) block over the head axis is not
            pl.BlockSpec((1, ps, Dp),
                         lambda b, h, j, tbl, ln: (tbl[b, j], 0, h)),
            pl.BlockSpec((1, ps, Dp),
                         lambda b, h, j, tbl, ln: (tbl[b, j], 0, h)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, Dp),
                               lambda b, h, j, tbl, ln: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, Dp), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, Dp), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(tables.astype(jnp.int32), lens.astype(jnp.int32), qg,
      k_pages.reshape(NP, ps, Hkv * Dp), v_pages.reshape(NP, ps, Hkv * Dp))
    return out.reshape(B, Hq, Dp)[..., :D]


def paged_attention(q, k_pages, v_pages, tables, lens, *, window=None,
                    logit_softcap=0.0, impl: str = "jnp",
                    interpret: bool | None = None):
    """Dispatch: ``impl`` "jnp" (gather reference — the CPU hot path) or
    "pallas" (the scalar-prefetch kernel; interpret-mode off TPU)."""
    if impl == "pallas":
        if interpret is None:
            interpret = jax.default_backend() != "tpu"
        return paged_attention_pallas(q, k_pages, v_pages, tables, lens,
                                      window=window,
                                      logit_softcap=logit_softcap,
                                      interpret=interpret)
    return paged_attention_ref(q, k_pages, v_pages, tables, lens,
                               window=window, logit_softcap=logit_softcap)
