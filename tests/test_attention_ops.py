"""Flash-attention fwd/bwd op test matrix (xform-style axes).

Every case checks the Pallas kernel pipeline — forward AND the custom-vjp
gradients (dq/dk/dv via ``jax.grad``) — against ``naive_attention``
autodiff, running in interpret mode on CPU (``kernels.ops`` gates on the
backend). Axes: seq length {one block, ragged/non-block-multiple, long},
head_dim {64, 128, 72→padded-to-128}, GQA group sizes {1, 2, 4},
causal × sliding-window × logit-softcap, and bf16 inputs with f32
tolerances.

The split mirrors the repo's CI lanes: a smoke subset stays unmarked for
the PR lane; the rest carries ``slow`` (nightly runs everything) and is
additionally skipped under ``REPRO_ATTN_SMOKE=1``, the same env pattern
as hwa-lint/fault-check. The band-masking hypothesis sweep rides the
usual ``importorskip`` (hypothesis is a dev-only dep).
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.models.attention import naive_attention

SMOKE = os.environ.get("REPRO_ATTN_SMOKE") == "1"
B = 2


def _case(S, Hq, Hkv, D, window, cap, dtype, smoke=False, blocks=(64, 64)):
    """``blocks`` None leaves the blocks to the shapes (``flash_blocks``):
    a KV head's whole GQA group per grid step, blocks of up to 1024."""
    marks = []
    if not smoke:
        marks.append(pytest.mark.slow)
        if SMOKE:
            marks.append(pytest.mark.skip(
                reason="REPRO_ATTN_SMOKE=1: PR-lane smoke subset only"))
    return pytest.param(
        S, Hq, Hkv, D, window, cap, dtype, blocks, marks=marks,
        id=f"S{S}-H{Hq}kv{Hkv}-D{D}-w{window}-cap{cap}-{dtype}"
        + ("" if blocks else "-auto"))


# One axis varies per row (plus a kitchen-sink case); smoke rows cover
# every axis at least once.
MATRIX = [
    # seq: exactly one block / ragged (pads 80→128) / long (multi-block)
    _case(64, 4, 4, 64, None, 0.0, "float32", smoke=True),
    _case(80, 4, 2, 64, None, 0.0, "float32", smoke=True),
    _case(256, 4, 2, 64, None, 0.0, "float32"),
    # head_dim: native 128 / padded 72→128 (64 covered above)
    _case(128, 4, 2, 128, None, 0.0, "float32"),
    _case(128, 4, 2, 72, None, 0.0, "float32", smoke=True),
    # GQA group sizes 1 and 4 (G=2 covered above)
    _case(128, 4, 4, 64, None, 0.0, "float32"),
    _case(128, 4, 1, 64, None, 0.0, "float32"),
    # causal × window × softcap
    _case(128, 4, 2, 64, 32, 0.0, "float32"),
    _case(128, 4, 2, 64, None, 15.0, "float32"),
    _case(128, 4, 2, 64, 24, 15.0, "float32", smoke=True),
    # everything at once: ragged + padded head_dim + G=4 + window + cap
    _case(160, 4, 1, 72, 48, 8.0, "float32"),
    # bf16 inputs, f32 tolerances
    _case(128, 4, 2, 64, None, 0.0, "bfloat16", smoke=True),
    _case(128, 4, 4, 64, 32, 15.0, "bfloat16"),
    # blocks from the shapes, the GQA group per grid step: G = 2, 4, 8;
    # S = 512 and 1024 (blocks above 128); ragged 900 → 1024 with 256-row
    # q blocks; a window under the largest block; softcap; bf16
    _case(512, 4, 2, 64, None, 0.0, "float32", blocks=None),
    _case(1024, 8, 2, 64, None, 0.0, "float32", blocks=None),
    _case(1024, 8, 1, 64, None, 0.0, "float32", blocks=None),
    _case(900, 8, 2, 64, None, 0.0, "float32", blocks=None),
    _case(1024, 8, 2, 64, 200, 0.0, "float32", blocks=None),
    _case(512, 8, 2, 64, None, 15.0, "float32", blocks=None),
    _case(1024, 8, 2, 64, 300, 15.0, "bfloat16", blocks=None),
]

MATRIX_ARGS = "S,Hq,Hkv,D,window,cap,dtype,blocks"


def _mk(S, Hq, Hkv, D, dtype, seed=0):
    ks = jax.random.split(jax.random.key(seed), 4)
    q = jax.random.normal(ks[0], (B, S, Hq, D)).astype(dtype)
    k = jax.random.normal(ks[1], (B, S, Hkv, D)).astype(dtype)
    v = jax.random.normal(ks[2], (B, S, Hkv, D)).astype(dtype)
    # a fixed f32 cotangent: sum(out * w) exercises every output element
    w = jax.random.normal(ks[3], (B, S, Hq, D), jnp.float32)
    return q, k, v, w


def _naive(q, k, v, window, cap):
    S, T = q.shape[1], k.shape[1]
    qp = jnp.arange(S)[None].repeat(q.shape[0], 0)
    kp = jnp.arange(T)[None].repeat(k.shape[0], 0)
    return naive_attention(q, k, v, qp, kp, window=window, logit_softcap=cap)


def _tols(dtype):
    # bf16 operands, f32 accumulation on both sides → f32-scale tolerances
    # loosened for the bf16 input rounding itself
    return (3e-2, 3e-2) if dtype == "bfloat16" else (2e-5, 2e-5)


@pytest.mark.parametrize(MATRIX_ARGS, MATRIX)
def test_forward_matches_naive(S, Hq, Hkv, D, window, cap, dtype, blocks):
    q, k, v, _ = _mk(S, Hq, Hkv, D, dtype)
    block_q, block_k = blocks or (None, None)
    out = kops.flash_attention(q, k, v, window=window, logit_softcap=cap,
                               block_q=block_q, block_k=block_k)
    ref = _naive(q, k, v, window, cap)
    rtol, atol = _tols(dtype)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=rtol, atol=atol)


@pytest.mark.parametrize(MATRIX_ARGS, MATRIX)
def test_grads_match_naive(S, Hq, Hkv, D, window, cap, dtype, blocks):
    q, k, v, w = _mk(S, Hq, Hkv, D, dtype)
    block_q, block_k = blocks or (None, None)

    def f_flash(q, k, v):
        out = kops.flash_attention(q, k, v, window=window, logit_softcap=cap,
                                   block_q=block_q, block_k=block_k)
        return jnp.sum(out.astype(jnp.float32) * w)

    def f_naive(q, k, v):
        return jnp.sum(_naive(q, k, v, window, cap).astype(jnp.float32) * w)

    got = jax.grad(f_flash, (0, 1, 2))(q, k, v)
    want = jax.grad(f_naive, (0, 1, 2))(q, k, v)
    rtol, atol = _tols(dtype)
    for name, g, r in zip(("dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(r, np.float32),
                                   rtol=rtol, atol=atol, err_msg=name)


def test_flash_pallas_direct_grad():
    """The acceptance headline, without the ops.py pad/slice wrapper:
    ``jax.grad`` straight through ``flash_attention_pallas`` (interpret
    mode) matches naive autodiff."""
    S, Hq, Hkv, D = 128, 4, 2, 128
    q, k, v, w = _mk(S, Hq, Hkv, D, "float32")

    def f_flash(q, k, v):
        out = flash_attention_pallas(q, k, v, causal=True, window=32,
                                     logit_softcap=10.0, block_q=64,
                                     block_k=64, interpret=True)
        return jnp.sum(out * w)

    def f_naive(q, k, v):
        return jnp.sum(_naive(q, k, v, 32, 10.0) * w)

    got = jax.grad(f_flash, (0, 1, 2))(q, k, v)
    want = jax.grad(f_naive, (0, 1, 2))(q, k, v)
    for name, g, r in zip(("dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=2e-5, atol=2e-5, err_msg=name)


def _fully_masked_rows(S, T, window):
    """Row i sees keys in [i-window+1, min(i, T-1)]; the band is empty —
    fully masked — once i - (T - 1) >= window."""
    return np.arange(S) - (T - 1) >= window


def test_masked_row_regression():
    """The `_finalize` l==0 fix: queries past the key horizon of a
    sliding window produce EXACTLY zero output rows and zero gradients —
    no NaN/Inf from the −1e30 fill, no bogus uniform-mean rows.

    naive_attention softmaxes a fully-masked row into a uniform mean (no
    l==0 guard), so the oracle here is ``kref.attention_ref``, which
    zeroes such rows like the kernel does.
    """
    S, T, Hq, Hkv, D, window = 128, 64, 4, 2, 64, 16
    ks = jax.random.split(jax.random.key(3), 4)
    q = jax.random.normal(ks[0], (B, S, Hq, D))
    k = jax.random.normal(ks[1], (B, T, Hkv, D))
    v = jax.random.normal(ks[2], (B, T, Hkv, D))
    w = jax.random.normal(ks[3], (B, S, Hq, D))
    dead = _fully_masked_rows(S, T, window)
    assert dead.any() and not dead.all()

    out = kops.flash_attention(q, k, v, window=window, block_q=64,
                               block_k=64)
    ref = kref.attention_ref(q, k, v, window=window)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    assert not np.asarray(out)[:, dead].any(), \
        "fully-masked rows must be exactly zero"

    def f(q, k, v):
        o = kops.flash_attention(q, k, v, window=window, block_q=64,
                                 block_k=64)
        return jnp.sum(o * w)

    dq, dk, dv = jax.grad(f, (0, 1, 2))(q, k, v)
    for name, g in (("dq", dq), ("dk", dk), ("dv", dv)):
        assert np.isfinite(np.asarray(g)).all(), f"{name} has non-finite"
    assert not np.asarray(dq)[:, dead].any(), \
        "fully-masked query rows must have exactly zero dq"


# ---------------------------------------------------------------- hypothesis
# band-masking invariant sweep — dev-only dep, slow lane (same split as
# tests/test_kernels.py)

@pytest.mark.slow
def test_band_masking_invariant_property():
    pytest.importorskip("hypothesis", reason="hypothesis not installed "
                        "(see requirements-dev.txt)")
    from hypothesis import given, settings, strategies as st

    @given(st.integers(2, 4), st.integers(1, 2),
           st.sampled_from([8, 16, 24]), st.integers(0, 1000))
    @settings(max_examples=10, deadline=None)
    def run(nq_blocks, nt_blocks, window, seed):
        # S > T so a sliding window strands the tail queries past the
        # key horizon: rows with i - (T-1) >= window are fully masked
        S, T = 64 * nq_blocks, 64 * nt_blocks
        if not _fully_masked_rows(S, T, window).any():
            return
        ks = jax.random.split(jax.random.key(seed), 4)
        q = jax.random.normal(ks[0], (1, S, 2, 64))
        k = jax.random.normal(ks[1], (1, T, 2, 64))
        v = jax.random.normal(ks[2], (1, T, 2, 64))
        w = jax.random.normal(ks[3], (1, S, 2, 64))
        dead = _fully_masked_rows(S, T, window)

        def f(q, k, v):
            o = kops.flash_attention(q, k, v, window=window, block_q=64,
                                     block_k=64)
            return jnp.sum(o * w), o

        (_, out), (dq, dk, dv) = jax.value_and_grad(
            f, (0, 1, 2), has_aux=True)(q, k, v)
        for name, x in (("out", out), ("dq", dq), ("dk", dk), ("dv", dv)):
            assert np.isfinite(np.asarray(x)).all(), f"{name} non-finite"
        assert not np.asarray(out)[:, dead].any()
        assert not np.asarray(dq)[:, dead].any()

    run()
