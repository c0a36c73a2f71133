"""Fused HWA weight-averaging kernels (Pallas, TPU target).

The paper's per-cycle hot spot is elementwise arithmetic over the full
parameter set (DESIGN.md §2). Two kernels:

1. ``wa_window_update_kernel`` — fused slide-window update. Naively the
   ring update is three HBM passes (read old slot + read/write sum;
   write slot; read sum + write avg ⇒ 6N reads + 3N writes). Fused, each
   VMEM tile does::

       old       = ring[idx, tile]            (read)
       total'    = total + new - full*old     (read total, read new)
       ring[idx] = new                        (write)
       avg       = total' * inv_count         (write; total' written too)

   ⇒ 3N reads + 3N writes (total/ring-slot/avg), one pass. The ring slot
   index is scalar-prefetched so the BlockSpec index_map can address ring
   row ``idx`` directly in HBM — the untouched I−1 rows are never moved;
   the ``full``/``inv_count`` scalars ride in SMEM.

2. ``online_mean_kernel`` — K-replica mean (W̄ = (1/K)Σ W^k) fused with
   the f32 cast, tiled so each program reads K sub-tiles and writes one.

3. ``wa_sync_fused_kernel`` — the ENTIRE sync in one pass over packed
   state: K-replica mean and slide-window update fused, so W̄ never
   round-trips through HBM. Each tile does::

       mean      = (1/K) Σ_k stacked[k, tile]   (K reads)
       old       = ring[idx, tile]              (read)
       total'    = total + mean - full*old      (read total)
       ring[idx] = mean                         (write — ring slot IS W̄)
       total'                                    (write)
       avg       = total' * inv_count           (write)

   ⇒ (K+2)·N reads + 3·N writes, vs (K+3)·N reads + 4·N writes for the
   two-kernel pipeline (mean: K reads + 1 write; update: 3 reads + 3
   writes) with an intermediate W̄ buffer in HBM. The caller recovers W̄
   for the replica restart as ``ring'[idx]``.

All kernels operate on 2-D (rows, 128·k) views. The packed path
(``repro.common.packing``) feeds them one tile-aligned buffer for the
whole parameter set — zero per-call padding; the legacy per-leaf wrappers
in ``ops.py`` flatten/pad each leaf. ``ref.py`` holds the jnp oracles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# VMEM tile: (8, 1024) f32 = 32 KiB per operand; 6 operands ≈ 192 KiB —
# comfortably within the ~16 MiB VMEM budget, wide enough to stream HBM.
TILE_ROWS = 8
TILE_COLS = 1024


# Scalars: ``idx`` is the one scalar-prefetch operand (an int32 (1,)
# array) so the ring BlockSpec index_map can address row ``idx``;
# ``full_flag``/``inv_count`` ride as an f32 (2,) operand in SMEM. (Mosaic
# has no scalar bitcast, so they cannot be packed into the i32 prefetch.)
# Encoder and decoder below are the single source of truth for that
# layout — every window-update kernel decodes through them.


def _pack_scalars(idx, full_flag, inv_count):
    return (jnp.reshape(idx.astype(jnp.int32), (1,)),
            jnp.stack([full_flag.astype(jnp.float32),
                       inv_count.astype(jnp.float32)]))


def _unpack_scalars(fscal_ref):
    """(full_flag, inv_count) as f32 scalars read from SMEM."""
    return fscal_ref[0], fscal_ref[1]


# Shared BlockSpecs: the ring is addressed at HBM row ``idx`` straight
# from the prefetched scalar (the untouched I−1 rows are never moved);
# flat operands tile the (R, C) plane; the f32 scalars sit whole in SMEM.
_RING_SPEC = pl.BlockSpec((1, TILE_ROWS, TILE_COLS),
                          lambda i, j, s: (s[0], i, j))
_FLAT_SPEC = pl.BlockSpec((TILE_ROWS, TILE_COLS), lambda i, j, s: (i, j))
_SMEM_SPEC = pl.BlockSpec(memory_space=pltpu.SMEM)


def _wa_window_update_kernel(idx_ref, fscal_ref, ring_ref, total_ref,
                             new_ref, ring_out_ref, total_out_ref, avg_ref):
    """One (TILE_ROWS, TILE_COLS) tile of the fused window update."""
    full, inv_count = _unpack_scalars(fscal_ref)
    old = ring_ref[0]                       # ring block is (1, rows, cols)
    new = new_ref[...]
    total = total_ref[...] + new - full * old
    ring_out_ref[0] = new
    total_out_ref[...] = total
    avg_ref[...] = total * inv_count


def wa_window_update_2d(ring, total, new, idx, full_flag, inv_count,
                        *, interpret: bool = True):
    """ring: (I, R, C) f32; total/new: (R, C) f32; idx: scalar int32.

    Returns (ring', total', avg). R % TILE_ROWS == 0, C % TILE_COLS == 0.
    """
    I, R, C = ring.shape
    assert total.shape == (R, C) and new.shape == (R, C)
    assert R % TILE_ROWS == 0 and C % TILE_COLS == 0, (R, C)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(R // TILE_ROWS, C // TILE_COLS),
        in_specs=[_SMEM_SPEC, _RING_SPEC, _FLAT_SPEC, _FLAT_SPEC],
        out_specs=[_RING_SPEC, _FLAT_SPEC, _FLAT_SPEC],
    )
    ring_out, total_out, avg = pl.pallas_call(
        _wa_window_update_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(ring.shape, jnp.float32),
                   jax.ShapeDtypeStruct(total.shape, jnp.float32),
                   jax.ShapeDtypeStruct(total.shape, jnp.float32)],
        input_output_aliases={2: 0, 3: 1},   # ring->ring_out, total->total_out
        interpret=interpret,
    )(*_pack_scalars(idx, full_flag, inv_count), ring, total, new)
    return ring_out, total_out, avg


def _wa_sync_fused_kernel(idx_ref, fscal_ref, stacked_ref, ring_ref,
                          total_ref, ring_out_ref, total_out_ref, avg_ref,
                          *, inv_k: float):
    """One tile of the fused K-replica-mean + window update (whole sync)."""
    full, inv_count = _unpack_scalars(fscal_ref)
    mean = jnp.sum(stacked_ref[...].astype(jnp.float32), axis=0) * inv_k
    old = ring_ref[0]                       # ring block is (1, rows, cols)
    total = total_ref[...] + mean - full * old
    ring_out_ref[0] = mean                  # the slot IS W̄_e
    total_out_ref[...] = total
    avg_ref[...] = total * inv_count


def wa_sync_fused_2d(stacked, ring, total, idx, full_flag, inv_count,
                     *, interpret: bool = True):
    """Whole HWA sync, one launch. stacked: (K, R, C); ring: (I, R, C);
    total: (R, C) — all f32, R % TILE_ROWS == 0, C % TILE_COLS == 0.

    Returns (ring', total', avg) with ring'[idx] = W̄ = mean_k stacked[k]
    and avg = W̿. ring/total are donated (aliased in place).
    """
    K, R, C = stacked.shape
    assert ring.shape[1:] == (R, C) and total.shape == (R, C), \
        (stacked.shape, ring.shape, total.shape)
    assert R % TILE_ROWS == 0 and C % TILE_COLS == 0, (R, C)
    stacked_spec = pl.BlockSpec((K, TILE_ROWS, TILE_COLS),
                                lambda i, j, s: (0, i, j))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(R // TILE_ROWS, C // TILE_COLS),
        in_specs=[_SMEM_SPEC, stacked_spec, _RING_SPEC, _FLAT_SPEC],
        out_specs=[_RING_SPEC, _FLAT_SPEC, _FLAT_SPEC],
    )
    ring_out, total_out, avg = pl.pallas_call(
        functools.partial(_wa_sync_fused_kernel, inv_k=1.0 / K),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(ring.shape, jnp.float32),
                   jax.ShapeDtypeStruct(total.shape, jnp.float32),
                   jax.ShapeDtypeStruct(total.shape, jnp.float32)],
        input_output_aliases={3: 0, 4: 1},   # ring->ring_out, total->total_out
        interpret=interpret,
    )(*_pack_scalars(idx, full_flag, inv_count), stacked, ring, total)
    return ring_out, total_out, avg


def _wa_window_update_c_kernel(idx_ref, fscal_ref, ring_ref, total_ref,
                               comp_ref, new_ref, ring_out_ref,
                               total_out_ref, comp_out_ref, avg_ref):
    """Compressed-ring tile: ring stored in a narrow dtype (bf16), total
    f32 with Kahan compensation. The down/up-casts ride the same single
    pass — every byte is already in VMEM."""
    full, inv_count = _unpack_scalars(fscal_ref)
    old = ring_ref[0].astype(jnp.float32)
    slot = new_ref[...].astype(ring_out_ref.dtype)
    stored = slot.astype(jnp.float32)
    total0 = total_ref[...]
    y = (stored - full * old) - comp_ref[...]
    total = total0 + y
    ring_out_ref[0] = slot
    total_out_ref[...] = total
    comp_out_ref[...] = (total - total0) - y
    avg_ref[...] = total * inv_count


def wa_window_update_c_2d(ring, total, comp, new, idx, full_flag, inv_count,
                          *, interpret: bool = True):
    """Compressed-ring fused window update. ring: (I, R, C) bf16;
    total/comp/new: (R, C) f32. Returns (ring', total', comp', avg);
    ring/total/comp are donated (aliased in place). Matches
    ``ref.wa_window_update_c_ref`` bitwise (scales=None)."""
    I, R, C = ring.shape
    assert total.shape == (R, C) and comp.shape == (R, C) \
        and new.shape == (R, C)
    assert R % TILE_ROWS == 0 and C % TILE_COLS == 0, (R, C)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(R // TILE_ROWS, C // TILE_COLS),
        in_specs=[_SMEM_SPEC, _RING_SPEC, _FLAT_SPEC, _FLAT_SPEC,
                  _FLAT_SPEC],
        out_specs=[_RING_SPEC, _FLAT_SPEC, _FLAT_SPEC, _FLAT_SPEC],
    )
    ring_out, total_out, comp_out, avg = pl.pallas_call(
        _wa_window_update_c_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(ring.shape, ring.dtype),
                   jax.ShapeDtypeStruct(total.shape, jnp.float32),
                   jax.ShapeDtypeStruct(comp.shape, jnp.float32),
                   jax.ShapeDtypeStruct(total.shape, jnp.float32)],
        # ring->ring_out, total->total_out, comp->comp_out
        input_output_aliases={2: 0, 3: 1, 4: 2},
        interpret=interpret,
    )(*_pack_scalars(idx, full_flag, inv_count), ring, total, comp, new)
    return ring_out, total_out, comp_out, avg


def _wa_sync_fused_c_kernel(idx_ref, fscal_ref, stacked_ref, ring_ref,
                            total_ref, comp_ref, ring_out_ref,
                            total_out_ref, comp_out_ref, avg_ref, *,
                            inv_k: float):
    """Fused sync tile over a compressed ring: K-mean, narrow-dtype slot
    write, Kahan-compensated f32 total — one pass."""
    full, inv_count = _unpack_scalars(fscal_ref)
    mean = jnp.sum(stacked_ref[...].astype(jnp.float32), axis=0) * inv_k
    old = ring_ref[0].astype(jnp.float32)
    slot = mean.astype(ring_out_ref.dtype)
    stored = slot.astype(jnp.float32)
    total0 = total_ref[...]
    y = (stored - full * old) - comp_ref[...]
    total = total0 + y
    ring_out_ref[0] = slot
    total_out_ref[...] = total
    comp_out_ref[...] = (total - total0) - y
    avg_ref[...] = total * inv_count


def wa_sync_fused_c_2d(stacked, ring, total, comp, idx, full_flag,
                       inv_count, *, interpret: bool = True):
    """Whole compressed-ring HWA sync, one launch. stacked: (K, R, C)
    f32; ring: (I, R, C) bf16; total/comp: (R, C) f32. Returns (ring',
    total', comp', avg); W̄ is the caller's ``decode(ring'[idx])``."""
    K, R, C = stacked.shape
    assert ring.shape[1:] == (R, C) and total.shape == (R, C) \
        and comp.shape == (R, C)
    assert R % TILE_ROWS == 0 and C % TILE_COLS == 0, (R, C)
    stacked_spec = pl.BlockSpec((K, TILE_ROWS, TILE_COLS),
                                lambda i, j, s: (0, i, j))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(R // TILE_ROWS, C // TILE_COLS),
        in_specs=[_SMEM_SPEC, stacked_spec, _RING_SPEC, _FLAT_SPEC,
                  _FLAT_SPEC],
        out_specs=[_RING_SPEC, _FLAT_SPEC, _FLAT_SPEC, _FLAT_SPEC],
    )
    ring_out, total_out, comp_out, avg = pl.pallas_call(
        functools.partial(_wa_sync_fused_c_kernel, inv_k=1.0 / K),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(ring.shape, ring.dtype),
                   jax.ShapeDtypeStruct(total.shape, jnp.float32),
                   jax.ShapeDtypeStruct(comp.shape, jnp.float32),
                   jax.ShapeDtypeStruct(total.shape, jnp.float32)],
        # ring->ring_out, total->total_out, comp->comp_out
        input_output_aliases={3: 0, 4: 1, 5: 2},
        interpret=interpret,
    )(*_pack_scalars(idx, full_flag, inv_count), stacked, ring, total, comp)
    return ring_out, total_out, comp_out, avg


def _online_mean_kernel(x_ref, o_ref, *, inv_k: float):
    # x_ref: (K, TILE_ROWS, TILE_COLS) — reduce the replica axis in VMEM.
    o_ref[...] = jnp.sum(x_ref[...].astype(jnp.float32), axis=0) * inv_k


def online_mean_2d(stacked, *, interpret: bool = True,
                   inv_k: float | None = None):
    """stacked: (K, R, C) -> (R, C) f32 mean over axis 0.

    ``inv_k`` overrides the 1/K scale — the mesh-resident sync path uses
    it to compute a PARTIAL mean (local sum × 1/K_global) whose psum over
    the replica mesh axis is the global mean.
    """
    K, R, C = stacked.shape
    assert R % TILE_ROWS == 0 and C % TILE_COLS == 0, (R, C)
    grid = (R // TILE_ROWS, C // TILE_COLS)
    return pl.pallas_call(
        functools.partial(_online_mean_kernel,
                          inv_k=1.0 / K if inv_k is None else inv_k),
        grid=grid,
        in_specs=[pl.BlockSpec((K, TILE_ROWS, TILE_COLS),
                               lambda i, j: (0, i, j))],
        out_specs=pl.BlockSpec((TILE_ROWS, TILE_COLS), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((R, C), jnp.float32),
        interpret=interpret,
    )(stacked)
