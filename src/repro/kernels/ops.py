"""Jit'd public wrappers around the Pallas kernels.

Two families:

- **packed** (`*_packed`, `hwa_sync_packed`): operate on the contiguous
  tile-aligned buffers of ``repro.common.packing`` — one launch for the
  whole parameter set, zero per-call padding, ring/total donated in place.
  This is the hot path the WA state machine runs on.
- **per-leaf** (`wa_window_update`, `online_mean`): flatten + pad ONE
  parameter leaf per call. Kept as the benchmark baseline and for ad-hoc
  single-array use; a tree-mapped sync over L leaves costs L launches and
  re-pads (defeating donation) every call.

Plus head-dim padding for attention, and interpret-mode fallback off-TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.packing import ALIGN
from repro.kernels.flash_attention import (flash_attention_pallas,
                                           resolve_blocks)
from repro.kernels.wa_update import (TILE_COLS, TILE_ROWS, online_mean_2d,
                                     wa_sync_fused_2d, wa_sync_fused_c_2d,
                                     wa_window_update_2d,
                                     wa_window_update_c_2d)

# A packed buffer reshapes to (P // TILE_COLS, TILE_COLS) with the row
# count a TILE_ROWS multiple — the kernels' exact tiling, no padding.
assert ALIGN == TILE_ROWS * TILE_COLS, (ALIGN, TILE_ROWS, TILE_COLS)

#: ring dtypes the fused kernels handle in-kernel: f32 on the original
#: kernels, bf16 on the ``*_c`` (compressed, Kahan-total) variants. fp8
#: rings need per-block scale state and run the jnp path instead
#: (``launch.sync.packed`` / ``core.offline`` gate on this set, and
#: ``packed_sync_launch_budget`` mirrors it).
KERNEL_RING_DTYPES = (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16))


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _tiles(buf):
    """(… , P) -> (…, P // TILE_COLS, TILE_COLS) view, P % ALIGN == 0."""
    assert buf.shape[-1] % ALIGN == 0, buf.shape
    return buf.reshape(buf.shape[:-1] + (-1, TILE_COLS))


@functools.partial(jax.jit, donate_argnums=(0, 1))
def wa_window_update_packed(ring, total, new, idx, full_flag, inv_count):
    """Fused slide-window update over the WHOLE packed parameter set.

    ring: (I, P) f32; total/new: (P,) f32 with P % ALIGN == 0 (a
    ``packing.PackSpec.padded`` buffer). Exactly one kernel launch; ring
    and total are donated and updated in place, with no per-call
    padding. The reshapes to (rows, TILE_COLS) are bitcasts on the CPU;
    on a TPU, XLA relayouts the (…, P) buffers into the kernel's tiles.
    Returns (ring', total', avg).
    """
    I, Pn = ring.shape
    ring_o, total_o, avg = wa_window_update_2d(
        _tiles(ring), _tiles(total), _tiles(new),
        jnp.asarray(idx, jnp.int32), jnp.asarray(full_flag, jnp.float32),
        jnp.asarray(inv_count, jnp.float32), interpret=_interpret())
    return (ring_o.reshape(I, Pn), total_o.reshape(Pn), avg.reshape(Pn))


@functools.partial(jax.jit, static_argnames=("inv_k",))
def online_mean_packed(stacked, inv_k: float | None = None):
    """(K, P) packed replicas -> (P,) f32 mean. One kernel launch.

    With ``inv_k`` set, computes the partial mean ``sum × inv_k`` instead
    (the mesh-resident sync's pre-psum contribution when the replica
    stack is itself sharded over a mesh axis)."""
    K, Pn = stacked.shape
    return online_mean_2d(_tiles(stacked), interpret=_interpret(),
                          inv_k=inv_k).reshape(Pn)


@functools.partial(jax.jit, donate_argnums=(1, 2))
def hwa_sync_packed(stacked, ring, total, idx, full_flag, inv_count):
    """The whole HWA sync in ONE launch over packed state.

    stacked: (K, P) packed replicas; ring: (I, P); total: (P,) — f32,
    P % ALIGN == 0. Fuses the K-replica mean with the slide-window update:
    (K+2)·N reads + 3·N writes, no intermediate W̄ round-trip through HBM.
    Returns (ring', total', avg); W̄ for the replica restart is ring'[idx].
    """
    I, Pn = ring.shape
    ring_o, total_o, avg = wa_sync_fused_2d(
        _tiles(stacked), _tiles(ring), _tiles(total),
        jnp.asarray(idx, jnp.int32), jnp.asarray(full_flag, jnp.float32),
        jnp.asarray(inv_count, jnp.float32), interpret=_interpret())
    return (ring_o.reshape(I, Pn), total_o.reshape(Pn), avg.reshape(Pn))


@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def wa_window_update_packed_c(ring, total, comp, new, idx, full_flag,
                              inv_count):
    """Compressed-ring sibling of :func:`wa_window_update_packed`:
    ring (I, P) bf16, total/comp (P,) f32 (Kahan pair). One launch;
    ring/total/comp donated. Returns (ring', total', comp', avg)."""
    I, Pn = ring.shape
    ring_o, total_o, comp_o, avg = wa_window_update_c_2d(
        _tiles(ring), _tiles(total), _tiles(comp), _tiles(new),
        jnp.asarray(idx, jnp.int32), jnp.asarray(full_flag, jnp.float32),
        jnp.asarray(inv_count, jnp.float32), interpret=_interpret())
    return (ring_o.reshape(I, Pn), total_o.reshape(Pn),
            comp_o.reshape(Pn), avg.reshape(Pn))


@functools.partial(jax.jit, donate_argnums=(1, 2, 3))
def hwa_sync_packed_c(stacked, ring, total, comp, idx, full_flag,
                      inv_count):
    """Compressed-ring sibling of :func:`hwa_sync_packed`: the whole sync
    in ONE launch with the K-mean, the bf16 slot write and the
    Kahan-compensated f32 total fused. Returns (ring', total', comp',
    avg); W̄ for the replica restart is ``ring'[idx].astype(f32)``."""
    I, Pn = ring.shape
    ring_o, total_o, comp_o, avg = wa_sync_fused_c_2d(
        _tiles(stacked), _tiles(ring), _tiles(total), _tiles(comp),
        jnp.asarray(idx, jnp.int32), jnp.asarray(full_flag, jnp.float32),
        jnp.asarray(inv_count, jnp.float32), interpret=_interpret())
    return (ring_o.reshape(I, Pn), total_o.reshape(Pn),
            comp_o.reshape(Pn), avg.reshape(Pn))


def _pad_flat(x, tile=TILE_ROWS * TILE_COLS):
    n = int(np.prod(x.shape))
    pad = (-n) % tile
    flat = jnp.pad(x.reshape(-1), (0, pad))
    return flat.reshape(-1, TILE_COLS), n


@functools.partial(jax.jit, static_argnames=())
def wa_window_update(ring, total, new, idx, full_flag, inv_count):
    """Fused slide-window update for one parameter leaf.

    ring: (I, *shape) f32; total: (*shape) f32; new: (*shape) any float.
    Returns (ring', total', avg) in the original shapes (avg f32).
    """
    I = ring.shape[0]
    shape = total.shape
    ring2d = ring.reshape(I, -1)
    n = ring2d.shape[1]
    pad = (-n) % (TILE_ROWS * TILE_COLS)
    ring2d = jnp.pad(ring2d, ((0, 0), (0, pad))).reshape(I, -1, TILE_COLS)
    total2d, _ = _pad_flat(total)
    new2d, _ = _pad_flat(new.astype(jnp.float32))
    ring_o, total_o, avg_o = wa_window_update_2d(
        ring2d, total2d, new2d, jnp.asarray(idx, jnp.int32),
        jnp.asarray(full_flag, jnp.float32),
        jnp.asarray(inv_count, jnp.float32), interpret=_interpret())
    ring_out = ring_o.reshape(I, -1)[:, :n].reshape(ring.shape)
    total_out = total_o.reshape(-1)[:n].reshape(shape)
    avg = avg_o.reshape(-1)[:n].reshape(shape)
    return ring_out, total_out, avg


@jax.jit
def online_mean(stacked):
    """(K, *shape) -> mean over replicas, original dtype of ``stacked``."""
    K = stacked.shape[0]
    shape = stacked.shape[1:]
    x2d = stacked.reshape(K, -1)
    n = x2d.shape[1]
    pad = (-n) % (TILE_ROWS * TILE_COLS)
    x2d = jnp.pad(x2d, ((0, 0), (0, pad))).reshape(K, -1, TILE_COLS)
    out = online_mean_2d(x2d, interpret=_interpret())
    return out.reshape(-1)[:n].reshape(shape).astype(stacked.dtype)


def flash_attention(q, k, v, q_pos=None, k_pos=None, *, window=None,
                    logit_softcap=0.0, block_q=None, block_k=None):
    """run_attention-compatible wrapper (training/prefill layout:
    contiguous positions starting at 0). Pads head_dim to 128 and ragged
    sequence lengths up to a block multiple; differentiable end-to-end
    (the kernel's custom VJP composes with the pad/slice here). Blocks
    left as None follow from the shapes (``flash_blocks``); they tile
    the lengths padded to a multiple of 128, no further.

    Padding is grad-exact: padded key positions sit ABOVE every real
    query position, so the causal mask hides them; padded query rows are
    sliced off, their cotangent is zero, and zero dO contributes zero to
    dk/dv. Zero head-dim columns likewise produce zero gradient columns.
    """
    D, S, T = q.shape[-1], q.shape[1], k.shape[1]
    sm_scale = 1.0 / (D ** 0.5)
    pad_d = (-D) % 128
    if pad_d:
        padw = [(0, 0)] * 3 + [(0, pad_d)]
        q = jnp.pad(q, padw)
        k = jnp.pad(k, padw)
        v = jnp.pad(v, padw)
    bq, bk = resolve_blocks(block_q, block_k, S, T, q.shape[-1],
                            q.shape[2] // k.shape[2], window)
    pad_s, pad_t = (-S) % bq, (-T) % bk
    seqpad = lambda x, n: jnp.pad(x, ((0, 0), (0, n), (0, 0), (0, 0)))
    if pad_s:
        q = seqpad(q, pad_s)
    if pad_t:
        k = seqpad(k, pad_t)
        v = seqpad(v, pad_t)
    out = flash_attention_pallas(
        q, k, v, causal=True, window=window, logit_softcap=logit_softcap,
        block_q=bq, block_k=bk, sm_scale=sm_scale,
        interpret=_interpret())
    return out[:, :S, :, :D]
