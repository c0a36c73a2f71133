"""Hierarchical Weight Averaging — the paper's training framework.

State machine (Algorithms 1 & 2):

  every step   : each of the K replicas takes one optimizer step on its own
                 batch (different sampling orders)           [hwa_inner_step]
  every H steps: W̄_e = mean_k W^k ; every replica ← W̄_e ;
                 slide-window update → W̿_e                   [hwa_sync]

``inner`` state is stacked on a leading K axis (vmap on one device; the
``replica``/``pod`` mesh axis at scale). Special cases: K=1 ∧ I>1 →
slide-window offline WA (generalized SWA); K>1 ∧ I=1 → low-frequency
online WA (local SGD); K=1 ∧ I=1 → plain SGD.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.common.pytree import tree_mean_axis0
from repro.core.offline import WindowState, window_init
from repro.core.online import broadcast_to_replicas, online_average, \
    online_average_named
from repro.optim.base import Optimizer, apply_updates

PyTree = Any


@dataclasses.dataclass(frozen=True)
class HWAConfig:
    n_replicas: int = 2          # K (paper Table IV: 2-4; K=2 suffices)
    sync_period: int = 0         # H; 0 → one epoch (paper default H = N/B)
    window: int = 20             # I (paper Fig. 13: {20, 50})
    window_stride: int = 1       # sparse window (§III-B): every J-th cycle
    window_kind: str = "ring"    # ring | streaming (O(1)-memory, beyond paper)
    avg_opt_state: bool = False  # also average optimizer moments at sync
    use_kernels: bool = False    # fused Pallas WA update path
    outer_every: int = 1         # H₂, the two-level sync tree's outer
                                 # period: every H steps pods average
                                 # INTERNALLY; only every H·H₂ steps does
                                 # the cross-pod all-reduce + window push
                                 # run (launch/sync/topology.py TwoLevel).
                                 # 1 ≡ flat sync (every sync is global).
    resilient: bool = False      # elastic membership: exclude NaN'd /
                                 # diverged replicas from the K-mean via
                                 # an alive-mask with renormalized
                                 # 1/K_alive (bitwise identical to the
                                 # plain mean when all alive); the dead
                                 # replica restarts from W̄ with a fresh
                                 # optimizer (repro.resilience.health).
    max_param_rms: float | None = None
                                 # resilient-only divergence probe: a
                                 # replica whose overall parameter RMS
                                 # exceeds this is quarantined even if
                                 # finite (approximate on the packed
                                 # path — padding/replication counted;
                                 # None = finiteness check only).


@dataclasses.dataclass
class HWAState:
    inner: PyTree                # (K, ...) stacked replica params
    inner_opt: PyTree            # (K, ...) stacked optimizer state
    window_state: WindowState    # offline module state
    wa: PyTree                   # current W̿ (unstacked)
    cycle: jax.Array             # e — completed synchronization cycles
    step: jax.Array              # i — global optimizer steps taken


jax.tree_util.register_dataclass(
    HWAState,
    data_fields=["inner", "inner_opt", "window_state", "wa", "cycle", "step"],
    meta_fields=[])


def hwa_init(cfg: HWAConfig, params: PyTree, optimizer: Optimizer,
             ring_dtype=jnp.float32) -> HWAState:
    """All replicas start from the same initialization (Algorithm 1 line 1
    with a shared init; replicas diverge through data order).

    ``ring_dtype`` (dtype or ``f32``/``bf16``/``fp8`` token) selects the
    compressed slide-window state (``core.offline.window_init``); the f32
    default is bit-identical to the pre-compression path."""
    inner = broadcast_to_replicas(params, cfg.n_replicas)
    inner_opt = jax.vmap(optimizer.init)(inner)
    return HWAState(
        inner=inner, inner_opt=inner_opt,
        window_state=window_init(params, cfg.window, cfg.window_kind,
                                 ring_dtype=ring_dtype),
        wa=params, cycle=jnp.zeros((), jnp.int32),
        step=jnp.zeros((), jnp.int32))


def hwa_inner_step(cfg: HWAConfig, state: HWAState, batches: PyTree,
                   loss_fn: Callable, optimizer: Optimizer, lr) -> tuple[HWAState, PyTree]:
    """One SGD step per replica (Algorithm 1 lines 5-7). ``batches`` leaves
    have a leading K axis (different sampling order per replica)."""

    def one(params, opt, batch):
        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch)
        updates, opt2 = optimizer.update(grads, opt, params, lr)
        return apply_updates(params, updates), opt2, loss, metrics

    inner, inner_opt, losses, metrics = jax.vmap(one)(
        state.inner, state.inner_opt, batches)
    new_state = HWAState(inner=inner, inner_opt=inner_opt,
                         window_state=state.window_state, wa=state.wa,
                         cycle=state.cycle, step=state.step + 1)
    scalar = {k: jnp.mean(v) for k, v in metrics.items()
              if isinstance(v, jax.Array)
              and jnp.issubdtype(v.dtype, jnp.floating) and v.ndim <= 1}
    return new_state, {"loss": jnp.mean(losses),
                       "per_replica_loss": losses, **scalar}


def window_push_packed(cfg: HWAConfig, new_buf: jax.Array,
                       window_state: WindowState, cycle: jax.Array,
                       use_kernel: bool | None = None
                       ) -> tuple[WindowState, jax.Array, jax.Array]:
    """Packed-in/packed-out Algorithm-2 tail: push the packed W̄ buffer
    into the slide window unless the cycle misses ``window_stride``
    (sparse window, §III-B), with W̿ = W̄ until the first entry exists.

    Returns (window state, packed W̿_e, incremented cycle counter). Keeps
    everything in the packed (P,) layout so callers control when (and
    under what sharding) the final unpack happens. ``use_kernel``
    overrides ``cfg.use_kernels``; on multi-device meshes kernels are
    only safe inside a fully-manual shard_map on local buffer slices
    (``launch.sync.packed._local_packed_sync``) — a bare Pallas call is opaque
    to the GSPMD partitioner, which would run it per-shard with
    global-shape semantics and corrupt values.
    """
    from repro.core.offline import window_average_packed, \
        window_update_packed

    use_kernel = cfg.use_kernels if use_kernel is None else use_kernel
    new_cycle = cycle + 1
    take = jnp.mod(new_cycle - 1, cfg.window_stride) == 0

    def do_update(ws):
        return window_update_packed(ws, new_buf, use_kernel=use_kernel)

    def skip_update(ws):
        return ws, window_average_packed(ws)

    if cfg.window_stride == 1:
        new_ws, avg = do_update(window_state)
    else:
        new_ws, avg = jax.lax.cond(take, do_update, skip_update,
                                   window_state)
    avg = jnp.where(new_ws.count == 0, new_buf, avg)
    return new_ws, avg, new_cycle


def _window_push(cfg: HWAConfig, outer: PyTree, window_state: WindowState,
                 cycle: jax.Array) -> tuple[WindowState, PyTree, jax.Array]:
    """Tree-level wrapper of :func:`window_push_packed`: packs W̄ once,
    unpacks only the final W̿."""
    from repro.common.packing import pack, unpack

    new_ws, avg, new_cycle = window_push_packed(
        cfg, pack(outer, window_state.spec), window_state, cycle)
    return new_ws, unpack(avg, window_state.spec, like=outer), new_cycle


def _sync_fused(cfg: HWAConfig, state: HWAState
                ) -> tuple[PyTree, WindowState, PyTree, jax.Array]:
    """Whole sync in ONE fused kernel launch over packed state.

    Packs the K replicas into (K, P), then a single ``pallas_call``
    computes the replica mean AND the window update — (K+2) reads +
    3 writes, no W̄ round-trip through HBM. W̄ for the restart is read
    back from the just-written ring slot; only W̄/W̿ are unpacked.
    """
    from repro.common.packing import pack_stacked, unpack
    from repro.kernels import ops as kops

    ws = state.window_state
    I = ws.window
    stacked = pack_stacked(state.inner, ws.spec)
    idx = ws.next_idx
    full_flag = (ws.count >= I).astype(jnp.float32)
    new_count = jnp.minimum(ws.count + 1, I)
    inv_count = 1.0 / new_count.astype(jnp.float32)
    ring, total, avg = kops.hwa_sync_packed(
        stacked, ws.ring, ws.total, idx, full_flag, inv_count)
    new_ws = WindowState(ring=ring, total=total, count=new_count,
                         next_idx=jnp.mod(idx + 1, I), window=I,
                         kind=ws.kind, spec=ws.spec)
    outer = unpack(ring[idx], ws.spec)        # the slot just written IS W̄_e
    wa = unpack(avg, ws.spec)
    return outer, new_ws, wa, state.cycle + 1


def _sync_fused_c(cfg: HWAConfig, state: HWAState
                  ) -> tuple[PyTree, WindowState, PyTree, jax.Array]:
    """Compressed-ring (bf16) sibling of :func:`_sync_fused`: one fused
    launch with the K-mean, narrow slot write and Kahan-compensated f32
    total (``kernels.ops.hwa_sync_packed_c``). The restart W̄ is the
    DECODED just-written slot — every replica restarts from the same
    bf16-rounded mean, so the ring slot and the live replicas agree
    bitwise."""
    from repro.common.packing import pack_stacked, unpack
    from repro.kernels import ops as kops

    ws = state.window_state
    I = ws.window
    stacked = pack_stacked(state.inner, ws.spec)
    idx = ws.next_idx
    full_flag = (ws.count >= I).astype(jnp.float32)
    new_count = jnp.minimum(ws.count + 1, I)
    inv_count = 1.0 / new_count.astype(jnp.float32)
    comp = ws.comp if ws.comp is not None else jnp.zeros_like(ws.total)
    ring, total, comp, avg = kops.hwa_sync_packed_c(
        stacked, ws.ring, ws.total, comp, idx, full_flag, inv_count)
    new_ws = WindowState(ring=ring, total=total, count=new_count,
                         next_idx=jnp.mod(idx + 1, I), window=I,
                         kind=ws.kind, spec=ws.spec, comp=comp,
                         scales=ws.scales)
    outer = unpack(ring[idx], ws.spec)        # decoded slot IS W̄_e
    wa = unpack(avg, ws.spec)
    return outer, new_ws, wa, state.cycle + 1


def hwa_sync(cfg: HWAConfig, state: HWAState) -> tuple[HWAState, PyTree]:
    """End-of-cycle sync (Algorithm 1 lines 8-12 + Algorithm 2).

    Returns (new state, metrics): the ``cycle`` counter, and ``k_alive``
    on the resilient path. The replicas' divergence (paper Fig. 12) is
    not computed here, as it reads every inner weight once more: call
    :func:`repro.core.replica_divergence` on ``state.inner`` before the
    sync where it is wanted. The window update is skipped on cycles
    not matching ``window_stride`` (sparse window, §III-B). On the kernel
    path with a dense f32 ring window the sync is one fused launch
    (:func:`_sync_fused`); otherwise mean and window update run as two
    packed single-launch steps.

    With ``cfg.resilient`` the mean is the alive-masked elastic mean
    (``repro.resilience.health``): a NaN'd or diverged replica is
    excluded from W̄, restarts from W̄ like everyone else, and gets its
    per-replica optimizer slots zeroed (fresh init) instead of carrying
    poisoned moments into the next cycle. Bitwise identical to the
    non-resilient jnp path when every replica is healthy; the Pallas
    kernels are bypassed (they cannot mask) and the alive count is
    reported as the ``k_alive`` metric.
    """
    ws = state.window_state
    alive = None
    if cfg.resilient:
        from repro.resilience.health import (masked_mean_axis0,
                                             quarantine_opt_state,
                                             replica_alive_mask)
        alive = replica_alive_mask(state.inner, max_rms=cfg.max_param_rms)
        outer = masked_mean_axis0(state.inner, alive)
        window_state, wa, cycle = _window_push(cfg, outer,
                                               state.window_state,
                                               state.cycle)
    elif (cfg.use_kernels and ws.kind == "ring" and cfg.window_stride == 1
            and ws.ring is not None and ws.ring.dtype == jnp.float32):
        outer, window_state, wa, cycle = _sync_fused(cfg, state)
    elif (cfg.use_kernels and ws.kind == "ring" and cfg.window_stride == 1
            and ws.ring is not None and ws.ring.dtype == jnp.bfloat16):
        outer, window_state, wa, cycle = _sync_fused_c(cfg, state)
    elif cfg.use_kernels and jax.tree.leaves(state.inner):
        # two packed launches (mean, window push) with no intermediate
        # unpack/re-pack round-trip of the full parameter set
        from repro.common.packing import pack_stacked, unpack
        from repro.kernels import ops as kops
        buf = kops.online_mean_packed(pack_stacked(state.inner, ws.spec))
        outer = unpack(buf, ws.spec)
        window_state, avg, cycle = window_push_packed(cfg, buf, ws,
                                                      state.cycle)
        wa = unpack(avg, ws.spec)
    else:
        outer = online_average(state.inner)
        window_state, wa, cycle = _window_push(cfg, outer,
                                               state.window_state,
                                               state.cycle)
    inner = broadcast_to_replicas(outer, cfg.n_replicas)
    if cfg.avg_opt_state:
        if alive is not None:
            from repro.resilience.health import masked_mean_axis0
            opt_mean = masked_mean_axis0(state.inner_opt, alive)
        else:
            opt_mean = tree_mean_axis0(state.inner_opt)
        inner_opt = broadcast_to_replicas(opt_mean, cfg.n_replicas)
    elif alive is not None:
        # quarantine: dead replicas restart from W̄ (the broadcast above)
        # with fresh — zeroed — optimizer slots
        inner_opt = quarantine_opt_state(state.inner_opt, alive)
    else:
        inner_opt = state.inner_opt
    new_state = HWAState(inner=inner, inner_opt=inner_opt,
                         window_state=window_state, wa=wa,
                         cycle=cycle, step=state.step)
    metrics = {"cycle": cycle}
    if alive is not None:
        metrics["k_alive"] = jnp.sum(alive.astype(jnp.int32))
    return new_state, metrics


# ------------------------------------------------- mesh-native (per-replica)
#
# The functions below are the *local* view of Algorithms 1 & 2: they see one
# replica's unstacked params and communicate through a named axis (the
# ``replica`` mesh axis under shard_map, or a vmap axis_name on one device).
# The stacked functions above and these local ones compute identical math —
# tests/mesh_hwa_check.py verifies it numerically on a forced-host mesh.


def hwa_local_inner_step(params: PyTree, opt_state: PyTree, batch: PyTree,
                         loss_fn: Callable, optimizer: Optimizer, lr
                         ) -> tuple[PyTree, PyTree, jax.Array, dict]:
    """One replica's SGD step (Algorithm 1 lines 5-7), no leading K axis.

    Deliberately collective-free over the replica axis: inter-replica
    traffic may only happen in :func:`hwa_sync_named`, every H steps.
    """
    (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
        params, batch)
    updates, opt2 = optimizer.update(grads, opt_state, params, lr)
    return apply_updates(params, updates), opt2, loss, metrics


def hwa_sync_named(cfg: HWAConfig, params: PyTree,
                   window_state: WindowState, cycle: jax.Array,
                   axis_name: str = "replica"
                   ) -> tuple[PyTree, WindowState, PyTree, jax.Array]:
    """Named-axis end-of-cycle sync: W̄_e = pmean(W^k) over ``axis_name``
    — the single inter-replica collective of the whole cycle — then the
    slide-window update, computed identically (replica-invariantly) on
    every replica since pmean leaves all replicas with the same W̄_e.

    Returns (restarted params, window state, W̿_e, new cycle counter).

    .. warning:: Safe under ``vmap(axis_name=...)``; do NOT call inside a
       partial-auto ``shard_map`` on jax 0.4.x — the window push packs W̄
       from auto-sharded leaves, and XLA miscompiles that assembly in
       manual subgroups (values come back 2×, the IsManualSubgroup bug
       class). The mesh-native sync bundle
       (``launch.sync.bundles.make_mesh_hwa_sync_step``) therefore runs the
       WHOLE sync — psum, window push, unpack — inside a FULLY-manual
       shard_map over a shard-aware packed layout (no auto axes, no
       subgroup to miscompile, no assembly collectives); use that
       structure on meshes.
    """
    outer = online_average_named(params, axis_name)
    new_ws, wa, new_cycle = _window_push(cfg, outer, window_state, cycle)
    return outer, new_ws, wa, new_cycle
