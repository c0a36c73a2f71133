"""Compile every Pallas kernel of the main path for a TPU v5e, no chip
attached: the chip's compiler (Mosaic) runs against a described
``v5e:2x2`` topology, so a tiling or VMEM refusal fails here instead of
on the chip. Shapes are granite-3-2b's published widths (d_model 2048,
32 query / 8 KV heads, head_dim 64 padded to 128, vocab 49,155).

Nothing here runs a kernel: compiling proves only that Mosaic accepts
it. The topology is described inside a module fixture (never at import),
so every pytest-xdist worker collects the same tests and only the worker
running this file loads the TPU library.
"""
import os
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.common.packing import pack_spec
from repro.configs import get_config
from repro.kernels import ops
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.paged_attention import paged_attention_pallas
from repro.kernels.wa_update import wa_sync_fused_2d
from repro.models.registry import build_model

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

GRANITE = get_config("granite-3-2b").with_(n_layers=1)
HQ, HKV = GRANITE.n_heads, GRANITE.n_kv_heads
D = 128                  # head_dim 64, padded to the 128-lane MXU width
SEQ = 2048
K, WINDOW = 2, 2


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import compilation_cache as cc
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:       # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def packed_len():
    """Padded length P of granite's packed parameter buffer (1 layer)."""
    params_abs, _ = build_model(GRANITE).abstract()
    return pack_spec(params_abs).padded


@pytest.fixture
def compiled_kernels(monkeypatch):
    """The ``ops`` wrappers pick interpret mode off-TPU; steer them to
    the compiled kernel for a described chip."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args, **jit_kw):
    return jax.jit(fn, **jit_kw).lower(*args).compile()


def _n_kernels(compiled) -> int:
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


def _window_args(one_chip, P, ring_dtype=jnp.float32):
    f32 = lambda shape: _sds(shape, jnp.float32, one_chip)
    scalar = lambda dt: _sds((), dt, one_chip)
    return (_sds((WINDOW, P), ring_dtype, one_chip), f32((P,)),
            scalar(jnp.int32), scalar(jnp.float32), scalar(jnp.float32),
            f32)


def test_wa_window_update_packed_compiles(one_chip, packed_len,
                                          compiled_kernels):
    ring, total, idx, full, inv, f32 = _window_args(one_chip, packed_len)
    c = _compile(ops.wa_window_update_packed.__wrapped__, ring, total,
                 f32((packed_len,)), idx, full, inv, donate_argnums=(0, 1))
    assert _n_kernels(c) == 1


def test_wa_window_update_packed_c_compiles(one_chip, packed_len,
                                            compiled_kernels):
    ring, total, idx, full, inv, f32 = _window_args(one_chip, packed_len,
                                                    jnp.bfloat16)
    c = _compile(ops.wa_window_update_packed_c.__wrapped__, ring, total,
                 f32((packed_len,)), f32((packed_len,)), idx, full, inv,
                 donate_argnums=(0, 1, 2))
    assert _n_kernels(c) == 1


def test_hwa_sync_packed_compiles(one_chip, packed_len, compiled_kernels):
    """The whole HWA sync (K-mean fused with the window update) as the
    single-device trainer runs it: ``hwa_sync_packed``."""
    ring, total, idx, full, inv, f32 = _window_args(one_chip, packed_len)
    c = _compile(ops.hwa_sync_packed.__wrapped__, f32((K, packed_len)),
                 ring, total, idx, full, inv, donate_argnums=(1, 2))
    assert _n_kernels(c) == 1
    assert c.memory_analysis() is not None


def test_wa_sync_fused_2d_ragged_tree_compiles(one_chip):
    """The raw 2-D kernel on a small ragged packed layout (moved here
    from the packing tests: compiled, never interpreted)."""
    from repro.kernels.wa_update import TILE_COLS, TILE_ROWS
    rows = 3 * TILE_ROWS
    f32 = lambda shape: _sds(shape, jnp.float32, one_chip)
    c = _compile(
        lambda s, r, t, i, f, n: wa_sync_fused_2d(s, r, t, i, f, n,
                                                  interpret=False),
        f32((K, rows, TILE_COLS)), f32((WINDOW, rows, TILE_COLS)),
        f32((rows, TILE_COLS)), _sds((), jnp.int32, one_chip),
        _sds((), jnp.float32, one_chip), _sds((), jnp.float32, one_chip))
    assert _n_kernels(c) == 1


def _qkv(one_chip, batch=1):
    bf16 = lambda h: _sds((batch, SEQ, h, D), jnp.bfloat16, one_chip)
    return bf16(HQ), bf16(HKV), bf16(HKV)


def _flash(q, k, v):
    return flash_attention_pallas(q, k, v, causal=True,
                                  sm_scale=64 ** -0.5, interpret=False)


def test_flash_forward_compiles(one_chip):
    c = _compile(_flash, *_qkv(one_chip))
    assert _n_kernels(c) == 1


def test_flash_backward_compiles(one_chip):
    """jax.grad through the custom VJP: 1 forward + 2 backward sweeps."""
    def loss(q, k, v):
        return jnp.sum(_flash(q, k, v).astype(jnp.float32))
    c = _compile(jax.grad(loss, argnums=(0, 1, 2)), *_qkv(one_chip))
    assert _n_kernels(c) == 3


@pytest.mark.parametrize("window", [None, 200])
def test_flash_cell_shapes_compile(one_chip, compiled_kernels, window):
    """granite-3-2b.k2-h4's attention as the train step calls it: K=2
    vmapped replicas × 4 sequences of 1024, head_dim 64, through
    ``ops.flash_attention`` with the blocks it picks (one KV head's GQA
    group per grid step); and a window under the chosen block_k. Forward
    and ``jax.grad`` compile, with the kernels the readers find by name,
    once each: 1 forward, and 1 forward + dq + dkv."""
    sys.path.insert(0, ROOT)
    from chipbench import trace as tr
    bf16 = lambda h: _sds((K, 4, 1024, h, 64), jnp.bfloat16, one_chip)
    qkv = bf16(HQ), bf16(HKV), bf16(HKV)
    attn = jax.vmap(lambda q, k, v: ops.flash_attention(q, k, v,
                                                        window=window))

    def loss(q, k, v):
        return jnp.sum(attn(q, k, v).astype(jnp.float32))
    names = ("_flash_kernel", "_dq_kernel", "_dkv_kernel")
    for fn, n_fwd, n_bwd in ((attn, 1, 0),
                             (jax.grad(loss, argnums=(0, 1, 2)), 1, 1)):
        c = _compile(fn, *qkv)
        found = tr.pallas_kernels(c.as_text(), names)
        assert {k: len(v) for k, v in found.items()} == \
            dict(zip(names, (n_fwd, n_bwd, n_bwd)))
        assert _n_kernels(c) == n_fwd + 2 * n_bwd


@pytest.mark.parametrize("G,D_pad", [(1, 128), (8, 128), (1, 256),
                                     (4, 256), (8, 256)])
def test_flash_chosen_blocks_compile(one_chip, G, D_pad):
    """The blocks ``flash_blocks`` picks stay within Mosaic's scoped VMEM
    for other group sizes and head dims, with f32 inputs (the widest
    blocks): forward and ``jax.grad``, 2048 tokens."""
    f32 = lambda h: _sds((1, SEQ, h, D_pad), jnp.float32, one_chip)
    qkv = f32(2 * G), f32(2), f32(2)

    def loss(q, k, v):
        return jnp.sum(flash_attention_pallas(q, k, v, causal=True,
                                              interpret=False))
    assert _n_kernels(_compile(jax.grad(loss, argnums=(0, 1, 2)),
                               *qkv)) == 3


def test_paged_decode_compiles(one_chip):
    """One decode token per sequence against a 16-token-page pool: 4
    sequences of up to 256 positions, head_dim 64 (padded in-wrapper)."""
    B, ps, tw = 4, 16, 16
    n_pages = B * tw + 1
    c = _compile(
        lambda q, kp, vp, t, n: paged_attention_pallas(q, kp, vp, t, n,
                                                       interpret=False),
        _sds((B, HQ, 64), jnp.bfloat16, one_chip),
        _sds((n_pages, ps, HKV, 64), jnp.bfloat16, one_chip),
        _sds((n_pages, ps, HKV, 64), jnp.bfloat16, one_chip),
        _sds((B, tw), jnp.int32, one_chip), _sds((B,), jnp.int32, one_chip))
    assert _n_kernels(c) == 1


def test_trace_readers_find_the_kernels_by_name(one_chip, packed_len,
                                                compiled_kernels):
    """The chip benchmark's readers find each kernel in a compiled
    program by its function name in the Mosaic body
    (``chipbench.trace.pallas_kernels``): flash forward, dq and dkv in
    the train step, the fused WA sync in the sync."""
    sys.path.insert(0, ROOT)
    from chipbench import trace as tr

    def loss(q, k, v):
        return jnp.sum(_flash(q, k, v).astype(jnp.float32))
    flash = _compile(jax.grad(loss, argnums=(0, 1, 2)), *_qkv(one_chip))
    ring, total, idx, full, inv, f32 = _window_args(one_chip, packed_len)
    sync = _compile(ops.hwa_sync_packed.__wrapped__, f32((K, packed_len)),
                    ring, total, idx, full, inv, donate_argnums=(1, 2))
    for compiled, kernels in (
            (flash, ("_flash_kernel", "_dq_kernel", "_dkv_kernel")),
            (sync, ("_wa_sync_fused_kernel",))):
        found = tr.pallas_kernels(compiled.as_text(), kernels)
        assert {k: len(v) for k, v in found.items()} == \
            {k: 1 for k in kernels}
