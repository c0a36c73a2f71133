"""Per-kernel allclose vs the pure-jnp oracles, with hypothesis sweeps
over shapes/dtypes (assignment deliverable c)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

# hypothesis-heavy: excluded from the CI tier1 PR lane (-m "not slow");
# the nightly full lane runs it
pytestmark = pytest.mark.slow

pytest.importorskip("hypothesis", reason="hypothesis not installed "
                    "(see requirements-dev.txt)")
from hypothesis import given, settings, strategies as st

from repro.kernels import ops as kops
from repro.kernels import ref as kref

SETTINGS = dict(max_examples=10, deadline=None)


@given(st.sampled_from([(7, 13), (128,), (1024,), (3, 5, 17), (8192,),
                        (2, 1024, 3)]),
       st.integers(2, 6), st.integers(0, 1000))
@settings(**SETTINGS)
def test_wa_window_update_shapes(shape, window, seed):
    ks = jax.random.split(jax.random.key(seed), 2)
    ring = jax.random.normal(ks[0], (window,) + shape, jnp.float32)
    total = jnp.sum(ring, 0)
    new = jax.random.normal(ks[1], shape, jnp.float32)
    idx = seed % window
    for full, cnt in [(1.0, window), (0.0, max(1, window - 2))]:
        got = kops.wa_window_update(ring, total, new, idx, full, 1.0 / cnt)
        want = kref.wa_window_update_ref(ring, total, new, idx, full,
                                         1.0 / cnt)
        for g, w in zip(got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=1e-6, atol=1e-6)


@given(st.integers(2, 4),
       st.sampled_from([(5,), (33, 7), (1024,), (2, 8, 128)]),
       st.sampled_from(["float32", "bfloat16"]), st.integers(0, 100))
@settings(**SETTINGS)
def test_online_mean_shapes_dtypes(k, shape, dtype, seed):
    x = jax.random.normal(jax.random.key(seed), (k,) + shape).astype(dtype)
    got = kops.online_mean(x)
    want = kref.online_mean_ref(x).astype(dtype)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-2 if dtype == "bfloat16" else 1e-6,
                               atol=1e-6)


def _oracle_case(B, S, Hq, Hkv, D, window, cap, dtype, blocks=(64, 64)):
    """64-row blocks as given by the caller, or None: the blocks follow
    from the shapes (``flash_blocks``) and the grid takes a KV head's
    whole GQA group per step."""
    case_id = "-".join(str(x) for x in (B, S, Hq, Hkv, D, window, cap,
                                        dtype))
    return pytest.param(B, S, Hq, Hkv, D, window, cap, dtype, blocks,
                        id=case_id if blocks else case_id + "-auto")


@pytest.mark.parametrize("B,S,Hq,Hkv,D,window,cap,dtype,blocks", [
    _oracle_case(1, 128, 2, 1, 16, None, 0.0, "float32"),
    _oracle_case(2, 128, 4, 2, 32, None, 50.0, "float32"),
    _oracle_case(1, 256, 2, 2, 16, 64, 0.0, "float32"),
    _oracle_case(1, 128, 4, 1, 8, 32, 30.0, "float32"),
    _oracle_case(2, 128, 4, 4, 64, None, 0.0, "bfloat16"),
    _oracle_case(1, 128, 8, 2, 24, None, 0.0, "float32"),  # D padded to 128
    # blocks from the shapes: GQA groups of 2, 4 and 8 per grid step
    _oracle_case(1, 512, 4, 2, 64, None, 0.0, "float32", None),
    _oracle_case(1, 1024, 8, 2, 64, None, 0.0, "float32", None),
    _oracle_case(1, 1024, 8, 1, 64, None, 0.0, "float32", None),
    # ragged: 900 rows pad to 1024, not a multiple of the 256-row q block
    _oracle_case(1, 900, 8, 2, 64, None, 0.0, "float32", None),
    # a window narrower than the largest block: block_k held to 256
    _oracle_case(1, 1024, 8, 2, 64, 200, 0.0, "float32", None),
    _oracle_case(1, 512, 8, 2, 64, None, 30.0, "float32", None),
    _oracle_case(2, 1024, 8, 2, 64, 300, 20.0, "bfloat16", None),
])
def test_flash_pallas_vs_oracle(B, S, Hq, Hkv, D, window, cap, dtype,
                                blocks):
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (B, S, Hq, D)).astype(dtype)
    k = jax.random.normal(ks[1], (B, S, Hkv, D)).astype(dtype)
    v = jax.random.normal(ks[2], (B, S, Hkv, D)).astype(dtype)
    block_q, block_k = blocks or (None, None)
    out = kops.flash_attention(q, k, v, window=window, logit_softcap=cap,
                               block_q=block_q, block_k=block_k)
    ref = kref.attention_ref(q, k, v, window=window, logit_softcap=cap)
    tol = 2e-2 if dtype == "bfloat16" else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


def test_flash_jnp_custom_vjp_grads():
    """jnp flash (custom VJP) gradient == naive autodiff gradient."""
    from repro.models.attention import flash_attention_jnp, naive_attention
    B, S, Hq, Hkv, D = 2, 128, 4, 2, 16
    ks = jax.random.split(jax.random.key(0), 4)
    q, k, v = (jax.random.normal(kk, (B, S, h, D))
               for kk, h in zip(ks, [Hq, Hkv, Hkv]))
    dout = jax.random.normal(ks[3], (B, S, Hq, D))
    pos = jnp.arange(S)
    for window, cap in [(None, 0.0), (32, 0.0), (None, 30.0), (48, 20.0)]:
        def fr(q, k, v):
            return jnp.sum(naive_attention(
                q, k, v, pos[None].repeat(B, 0), pos[None].repeat(B, 0),
                window=window, logit_softcap=cap) * dout)

        def ff(q, k, v):
            return jnp.sum(flash_attention_jnp(
                q, k, v, window=window, logit_softcap=cap,
                q_block=32, k_block=32) * dout)

        gr = jax.grad(fr, (0, 1, 2))(q, k, v)
        gf = jax.grad(ff, (0, 1, 2))(q, k, v)
        for a, b in zip(gr, gf):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)
