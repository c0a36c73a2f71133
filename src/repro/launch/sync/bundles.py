"""Step builders: plain data+tensor-parallel training/serving steps and
the HWA-stacked variants, with in/out shardings resolved from the
logical-dim trees. These are what the dry-run lowers and what real
launches run.

Split of the former ``launch/steps.py`` monolith (PR 4): this module
assembles StepBundles; the sync-topology abstraction lives in
``launch.sync.topology`` (Flat / TwoLevel), the mesh-resident packed
machinery in ``launch.sync.packed``, and the legacy GSPMD fallback in
``launch.sync.legacy``. ``repro.launch.steps`` remains a re-exporting
facade, so every pre-split import keeps working.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import warnings
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.analysis.contracts import sync_contract, train_contract
from repro.common.compat import shard_map
from repro.core.hwa import HWAConfig, hwa_local_inner_step
from repro.launch.sync.legacy import (check_legacy_assembly,
                                      make_legacy_mesh_sync_step,
                                      make_legacy_sync_step)
from repro.launch.sync.packed import (_axes_entry, _local_inner_sync,
                                      _local_packed_sync, _norm_entry,
                                      _packed_pspecs, _packed_shardings,
                                      choose_resident_spec,
                                      packed_sync_launch_budget)
from repro.launch.sync.topology import Flat, SyncTopology, TwoLevel
from repro.models.registry import LM
from repro.optim import adamw, apply_updates, sgd
from repro.sharding.rules import ShardingRules, stacked_replica_specs

PyTree = Any


def _prefix_dims(dim_tree, name):
    """Prepend a logical dim to every dims-tuple leaf (e.g. 'replica')."""
    is_dims = lambda t: isinstance(t, tuple) and all(
        isinstance(e, (str, type(None))) for e in t)
    return jax.tree.map(lambda t: (name,) + t, dim_tree, is_leaf=is_dims)


def opt_state_dims(opt_state_abs, param_dims):
    """Logical dims for optimizer state: moments mirror the params."""
    # adamw: {"m": params-like, "v": params-like, "count": scalar}
    # sgd(momentum): {"mu": params-like}
    out = {}
    for k, v in opt_state_abs.items():
        if k == "count":
            out[k] = ()
        else:
            out[k] = param_dims
    return out


@dataclasses.dataclass
class StepBundle:
    """A step function plus its abstract args and in/out shardings.

    ``pack_spec`` is set by the WA sync bundles: their window state (and
    returned W̿) lives in the packed layout of ``repro.common.packing``;
    consumers materialize leaf views with ``packing.unpack(buf,
    bundle.pack_spec)``.

    ``contract`` is the bundle's declarative SPMD contract
    (:class:`repro.analysis.contracts.BundleContract`), attached by the
    builder — it knows the topology, kernel gating and pack layout it
    chose, so the declaration (collective census, Pallas-launch budget,
    dtype discipline) is exact with no second source of truth.
    ``tools/hwa_lint.py`` checks it against the compiled program; None
    means only the universal baseline applies.
    """
    fn: Any
    abstract_args: tuple
    in_shardings: tuple
    out_shardings: Any
    donate_argnums: tuple = ()
    pack_spec: Any = None
    contract: Any = None

    def lower(self, mesh: Mesh):
        jitted = jax.jit(self.fn, in_shardings=self.in_shardings,
                         out_shardings=self.out_shardings,
                         donate_argnums=self.donate_argnums)
        with mesh:
            return jitted.lower(*self.abstract_args)


def _mk_optimizer(name: str):
    if name == "sgd":
        return sgd(momentum=0.9, weight_decay=5e-4)
    return adamw(weight_decay=0.1)


def make_train_step(lm: LM, rules: ShardingRules, batch_specs, batch_dims,
                    optimizer: str = "adamw", lr: float = 3e-4,
                    opt_rules: ShardingRules | None = None,
                    n_microbatches: int = 1) -> StepBundle:
    """Plain data+tensor-parallel train step (the 40-combo baseline).

    ``opt_rules`` lets the optimizer moments use a different (e.g. FSDP)
    rule table than the compute params. ``n_microbatches`` > 1 enables
    gradient accumulation: peak activation temps scale ~1/n_mb while the
    f32 grad accumulator is fully sharded — the lever that fits the ≥27B
    trainings into 16 GB/chip (EXPERIMENTS.md §Perf).
    """
    opt = _mk_optimizer(optimizer)
    params_abs, param_dims = lm.abstract()
    opt_abs = jax.eval_shape(opt.init, params_abs)
    o_dims = opt_state_dims(opt_abs, param_dims)
    opt_rules = opt_rules or rules
    loss_fn = lambda p, b: lm.loss(p, b, rules=rules)

    def step(params, opt_state, batch):
        if n_microbatches == 1:
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
        else:
            mb = jax.tree.map(
                lambda x: x.reshape((n_microbatches,
                                     x.shape[0] // n_microbatches)
                                    + x.shape[1:]), batch)

            def body(acc, mbatch):
                g_acc, l_acc, a_acc = acc
                (loss, metrics), g = jax.value_and_grad(
                    loss_fn, has_aux=True)(params, mbatch)
                g_acc = jax.tree.map(
                    lambda a, gi: a + gi.astype(jnp.float32), g_acc, g)
                return (g_acc, l_acc + metrics["loss"],
                        a_acc + metrics["acc"]), None

            zeros = jax.tree.map(
                lambda pp: jnp.zeros(pp.shape, jnp.float32), params)
            (g_sum, l_sum, a_sum), _ = jax.lax.scan(
                body, (zeros, jnp.zeros(()), jnp.zeros(())), mb)
            grads = jax.tree.map(
                lambda g, pp: (g / n_microbatches).astype(pp.dtype),
                g_sum, params)
            metrics = {"loss": l_sum / n_microbatches,
                       "aux": jnp.zeros(()),
                       "acc": a_sum / n_microbatches}
        updates, opt_state = opt.update(grads, opt_state, params, lr)
        params = apply_updates(params, updates)
        return params, opt_state, metrics

    p_sh = rules.tree_shardings(params_abs, param_dims)
    o_sh = opt_rules.tree_shardings(opt_abs, o_dims)
    b_sh = rules.tree_shardings(batch_specs, batch_dims)
    scalar_sh = NamedSharding(rules.mesh, P())
    m_sh = {"loss": scalar_sh, "aux": scalar_sh, "acc": scalar_sh}
    return StepBundle(
        fn=step, abstract_args=(params_abs, opt_abs, batch_specs),
        in_shardings=(p_sh, o_sh, b_sh),
        out_shardings=(p_sh, o_sh, m_sh),
        donate_argnums=(0, 1),
        contract=train_contract(notes="plain DP+TP train step"))


def make_prefill_step(lm: LM, rules: ShardingRules, batch_specs, batch_dims,
                      cache_abs, cache_dims) -> StepBundle:
    def step(params, cache, batch):
        return lm.prefill(params, cache, batch, rules=rules)

    params_abs, param_dims = lm.abstract()
    p_sh = rules.tree_shardings(params_abs, param_dims)
    c_sh = rules.tree_shardings(cache_abs, cache_dims)
    b_sh = rules.tree_shardings(batch_specs, batch_dims)
    logits_abs = jax.eval_shape(step, params_abs, cache_abs, batch_specs)[0]
    logits_dims = ("batch",) + (None,) * (len(logits_abs.shape) - 2) + ("vocab",)
    l_sh = rules.tree_shardings(logits_abs, logits_dims)
    return StepBundle(
        fn=step, abstract_args=(params_abs, cache_abs, batch_specs),
        in_shardings=(p_sh, c_sh, b_sh),
        out_shardings=(l_sh, c_sh),
        donate_argnums=(1,),
        contract=train_contract(notes="prefill step"))


def make_decode_step(lm: LM, rules: ShardingRules, token_specs, token_dims,
                     cache_abs, cache_dims) -> StepBundle:
    def step(params, cache, tokens):
        return lm.decode_step(params, cache, tokens, rules=rules)

    params_abs, param_dims = lm.abstract()
    p_sh = rules.tree_shardings(params_abs, param_dims)
    c_sh = rules.tree_shardings(cache_abs, cache_dims)
    t_sh = rules.tree_shardings(token_specs, token_dims)
    logits_abs = jax.eval_shape(step, params_abs, cache_abs, token_specs)[0]
    logits_dims = ("batch",) + (None,) * (len(logits_abs.shape) - 2) + ("vocab",)
    l_sh = rules.tree_shardings(logits_abs, logits_dims)
    return StepBundle(
        fn=step, abstract_args=(params_abs, cache_abs, token_specs),
        in_shardings=(p_sh, c_sh, t_sh),
        out_shardings=(l_sh, c_sh),
        donate_argnums=(1,),
        contract=train_contract(notes="decode step"))


# ------------------------------------------------------------- HWA steps


def _make_hwa_train_step(lm: LM, rules: ShardingRules, batch_specs,
                         batch_dims, hwa_cfg: HWAConfig,
                         optimizer: str = "adamw", lr: float = 3e-4,
                         opt_rules: ShardingRules | None = None,
                         n_microbatches: int = 1) -> StepBundle:
    """Inner HWA step: K independent replicas, stacked on the replica axis.

    Gradient all-reduce stays *inside* each replica's data shard; nothing
    crosses the replica/pod axis here — that is the H-fold comm saving.
    """
    opt = _mk_optimizer(optimizer)
    K = hwa_cfg.n_replicas
    params_abs, param_dims = lm.abstract()
    stacked_abs = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((K,) + s.shape, s.dtype), params_abs)
    stacked_dims = _prefix_dims(param_dims, "replica")
    opt_abs = jax.eval_shape(lambda p: jax.vmap(opt.init)(p), stacked_abs)
    o_dims = opt_state_dims(opt_abs, stacked_dims)
    if "count" in o_dims:          # adamw step counter, vmapped to (K,)
        o_dims["count"] = ("replica",)
    opt_rules = opt_rules or rules
    kbatch_abs = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((K,) + s.shape, s.dtype), batch_specs)
    kbatch_dims = _prefix_dims(batch_dims, "replica")

    def loss_fn(params, batch):
        return lm.loss(params, batch, rules=rules)

    def step(inner, inner_opt, batches):
        def one(params, opt_state, batch):
            if n_microbatches == 1:
                (loss, metrics), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params, batch)
            else:
                mb = jax.tree.map(
                    lambda x: x.reshape((n_microbatches,
                                         x.shape[0] // n_microbatches)
                                        + x.shape[1:]), batch)

                def body(acc, mbatch):
                    g_acc, l_acc = acc
                    (l, m), g = jax.value_and_grad(
                        loss_fn, has_aux=True)(params, mbatch)
                    g_acc = jax.tree.map(
                        lambda a, gi: a + gi.astype(jnp.float32), g_acc, g)
                    return (g_acc, l_acc + m["loss"]), None

                zeros = jax.tree.map(
                    lambda pp: jnp.zeros(pp.shape, jnp.float32), params)
                (g_sum, l_sum), _ = jax.lax.scan(
                    body, (zeros, jnp.zeros(())), mb)
                grads = jax.tree.map(
                    lambda g, pp: (g / n_microbatches).astype(pp.dtype),
                    g_sum, params)
                metrics = {"loss": l_sum / n_microbatches}
            updates, opt_state = opt.update(grads, opt_state, params, lr)
            return apply_updates(params, updates), opt_state, metrics["loss"]

        inner, inner_opt, losses = jax.vmap(one)(inner, inner_opt, batches)
        return inner, inner_opt, jnp.mean(losses)

    p_sh = rules.tree_shardings(stacked_abs, stacked_dims)
    o_sh = opt_rules.tree_shardings(opt_abs, o_dims)
    b_sh = rules.tree_shardings(kbatch_abs, kbatch_dims)
    scalar_sh = NamedSharding(rules.mesh, P())
    return StepBundle(
        fn=step, abstract_args=(stacked_abs, opt_abs, kbatch_abs),
        in_shardings=(p_sh, o_sh, b_sh),
        out_shardings=(p_sh, o_sh, scalar_sh),
        donate_argnums=(0, 1),
        # vmap path: replica independence is GSPMD-propagated, not
        # structural, so no replica-axis collective claim is declared
        contract=train_contract(notes="vmap HWA inner step"))


def _resolved_k_axes(rules: ShardingRules, K: int, topology: SyncTopology
                     ) -> tuple[str, ...]:
    """The mesh axes the rules actually shard the stacked K dim over,
    checked against the topology's replica axes (ORDER included — the
    two-level tree's 0-ULP composition needs pod-major, i.e. contiguous-
    pod, sharding of the K dim). May be empty for a Flat topology whose
    rules keep the stack device-local (K resident per device, no psum);
    a TwoLevel topology REQUIRES the sharding — without it there are no
    inner groups to reduce over."""
    k_entry = rules.spec(("replica",), (K,))
    k_axes = _norm_entry(k_entry[0] if len(k_entry) else None)
    if k_axes and k_axes != topology.replica_axes:
        raise ValueError(
            f"rules shard the stacked K dim over {k_axes} but the sync "
            f"topology expects {topology.replica_axes}; build the rules "
            f"with make_tp_rules(mesh, replica_axis="
            f"{topology.replica_axes!r})")
    if not k_axes and isinstance(topology, TwoLevel):
        raise ValueError(
            "two-level sync needs the stacked K dim sharded over "
            f"{topology.replica_axes}; build the rules with "
            f"make_tp_rules(mesh, replica_axis={topology.replica_axes!r})")
    return k_axes


def _check_outer_every(hwa_cfg: HWAConfig, topology: SyncTopology) -> None:
    """One source of truth for H₂: the driver schedules off
    ``topology.is_outer`` while ``HWAConfig.outer_every`` rides along in
    config records/checkpoints — refuse silently-disagreeing values."""
    if isinstance(topology, TwoLevel):
        if hwa_cfg.outer_every != topology.outer_every:
            raise ValueError(
                f"HWAConfig.outer_every={hwa_cfg.outer_every} disagrees "
                f"with TwoLevel.outer_every={topology.outer_every}; set "
                "both from the same value (the driver schedules off the "
                "topology)")
    elif hwa_cfg.outer_every != 1:
        raise ValueError(
            f"HWAConfig.outer_every={hwa_cfg.outer_every} would be "
            "silently ignored: this sync path is flat (every sync is "
            "outer). Use make_mesh_hwa_sync_step with a TwoLevel "
            "topology for the H·H₂ hierarchy, or leave outer_every at 1")


def _window_io(mesh: Mesh, spec, window: int, ring_dtype):
    """Ordered window-state slots of a sync bundle's argument list:
    ``(name, abstract, pspec, sharding)`` rows for ``ring``, the fp8
    ring's per-block ``scales`` (right after the ring it describes),
    ``total``, and the compressed ring's Kahan ``comp`` (right after the
    total it compensates). The f32 default contributes exactly the
    historical ``(ring, total)`` pair — THE one place the compressed
    argument ordering lives (``plan.window_state_args`` allocates real
    buffers in the same order)."""
    from repro.common.packing import window_aux_buffers, window_buffers
    ring_abs, total_abs = window_buffers(spec, window, ring_dtype,
                                         make=jax.ShapeDtypeStruct)
    scales_abs, comp_abs = window_aux_buffers(spec, window, ring_dtype,
                                              make=jax.ShapeDtypeStruct)
    rows = [("ring", ring_abs, _packed_pspecs(spec, 1),
             _packed_shardings(mesh, spec, lead_dims=1))]
    if scales_abs is not None:
        # (I, padded // align) shards over the same super-axis as the
        # ring: segment lengths are ALIGN multiples, so the per-shard
        # block counts divide exactly
        rows.append(("scales", scales_abs, _packed_pspecs(spec, 1),
                     _packed_shardings(mesh, spec, lead_dims=1)))
    rows.append(("total", total_abs, _packed_pspecs(spec),
                 _packed_shardings(mesh, spec)))
    if comp_abs is not None:
        rows.append(("comp", comp_abs, _packed_pspecs(spec),
                     _packed_shardings(mesh, spec)))
    return rows


def _precision_tokens(tok: str) -> tuple[str, ...]:
    """Allowed HLO dtype tokens for a precision token: what a bundle's
    floating args may be (ring storage) or its collective payloads may
    carry (comms) — always f32 plus the compressed dtype, if any."""
    from repro.common.quant import HLO_TOKENS
    extra = HLO_TOKENS[tok]
    return ("f32",) if extra == "f32" else ("f32", extra)


def _make_hwa_sync_step(lm: LM, rules: ShardingRules, hwa_cfg: HWAConfig,
                        ring_dtype=jnp.float32,
                        mesh_resident: bool | None = None) -> StepBundle:
    """Synchronization + window update: the once-per-H-steps collective.

    outer = mean over the replica axis (one all-reduce across pods);
    inner ← broadcast(outer); slide-window update on PACKED state: the
    ring is one (I, P) buffer and the total one (P,) buffer over the whole
    parameter set (``repro.common.packing``), held packed across the jit
    boundary so the donation of ring/total is a true in-place update
    step-to-step — no per-leaf launches, no per-call padding.

    Unlike the mesh-native builders below, the stacked K dim here may be
    LARGER than its mesh axis (several replicas resident per device);
    the local partial sums use the canonical halving order
    (``core.online.halving_sum_axis0``), which is what makes this flat
    path bit-comparable to the two-level composition.

    **pack_spec contract.** ``bundle.pack_spec`` is the layout the caller
    MUST allocate the window buffers from — ``ring = zeros((I,
    spec.padded), ring_dtype)``, ``total = zeros((spec.padded,), f32)`` —
    and the layout W̿/checkpointed state are expressed in. It is not
    always the default contiguous layout: the mesh-resident path below
    chooses a shard-aware layout (``spec.shards > 1``) whose ``padded``
    differs, so callers must never substitute their own
    ``pack_spec(params)``. Leaf views come back via ``packing.unpack(buf,
    bundle.pack_spec)``; checkpoints written through
    ``checkpoint.save_window_state`` record the layout and repack on load
    when it changed.

    **Donation invariants.** args 0-2 (stacked inner, ring, total) are
    donated: the caller's arrays are consumed every call and the returned
    buffers must be threaded into the next call (the trainer's steady
    state — this is what makes the ring update truly in place). Scalars
    (count, next_idx) are not donated.

    **Kernel gating / mesh residency.** On a single device the fused
    Pallas path runs as-is. On a multi-device mesh a bare ``pallas_call``
    is opaque to the GSPMD partitioner — XLA runs it per-shard with
    GLOBAL-shape semantics and silently corrupts values — so multi-device
    meshes default to the MESH-RESIDENT path: the whole sync runs inside
    a fully-manual ``shard_map`` where each device assembles and updates
    its local ``(I, P/shards)`` slice of a shard-aware packed layout
    (zero assembly collectives; see ``packed._local_packed_sync``),
    driving the Pallas kernel on true local shapes when ``use_kernels``
    and the jnp reference otherwise. Mixed tilings (FSDP's data/model
    splits, multi-dim placements included) take the GROUPED layout
    (``packed.choose_resident_spec`` → ``PackSpec.groups``): ring/total
    become PER-GROUP buffer tuples — allocate them with
    ``packing.window_buffers(bundle.pack_spec, I)`` — each sharded over
    its group's own super-axis, updated by one kernel launch per group,
    still with exactly one replica all-reduce and zero assembly
    collectives. The legacy GSPMD fallback (``launch.sync.legacy``) is
    now an explicitly-requested escape hatch (``mesh_resident=False``) or
    the last resort for layouts even the grouped chooser cannot align
    (zero-size leaves, params sharded over replica axes, indivisible
    tiles) — it pays one param-size assembly all-reduce per sync, and on
    multi-device CPU meshes it is a HARD ERROR (XLA 0.4.37's CPU
    partitioner miscompiles it; ``REPRO_ALLOW_LEGACY_ASSEMBLY=1``
    downgrades to a warning for HLO-introspection-only callers).
    ``mesh_resident`` forces the choice (True raises if no layout
    qualifies); None picks automatically.

    Variants (EXPERIMENTS.md §Perf pair 3): exact f32 ring (paper),
    compressed bf16/fp8 rings (``ring_dtype`` token or dtype — 2×/~4×
    window-HBM saving, Kahan-compensated f32 total, fp8 with per-block
    scales; the extra ``scales``/``comp`` args slot in as
    ``(inner, ring, [scales], total, [comp], count, next_idx)``), or
    hwa_cfg.window_kind == "streaming" (O(1) extra copies,
    windowed-running-mean approximation; always the jnp path — it is a
    two-pass rescale, not ring-shaped).
    """
    from repro.common.quant import is_compressed, wa_dtype, wa_token
    K = hwa_cfg.n_replicas
    I = hwa_cfg.window
    mesh = rules.mesh
    ring_dtype = wa_dtype(ring_dtype)
    tok = wa_token(ring_dtype)
    # this stacked/vmap path is flat-only; refuse a silently-ignored H₂
    _check_outer_every(hwa_cfg, Flat())
    streaming = hwa_cfg.window_kind == "streaming"
    use_kernel = hwa_cfg.use_kernels and mesh.size == 1
    params_abs, param_dims = lm.abstract()
    stacked_abs = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((K,) + s.shape, s.dtype), params_abs)
    stacked_dims = _prefix_dims(param_dims, "replica")
    scalar_i = jax.ShapeDtypeStruct((), jnp.int32)

    pspec_tree = rules.tree_specs(params_abs, param_dims)
    flat_specs = jax.tree.leaves(pspec_tree)
    flat_shapes = [tuple(l.shape) for l in jax.tree.leaves(params_abs)]
    k_entry = rules.spec(("replica",), (K,))
    k_axes = _norm_entry(k_entry[0] if len(k_entry) else None)
    spec = choose_resident_spec(mesh, params_abs, flat_specs, flat_shapes,
                                exclude=k_axes)
    if mesh_resident is None:
        mesh_resident = (mesh.size > 1 and not streaming
                         and spec is not None)
    if mesh_resident and (spec is None or streaming):
        raise ValueError("mesh-resident sync needs a ring window and "
                         "leaf tilings that align with packed ranges "
                         "(no single-super-axis OR grouped layout found)")

    if mesh_resident:
        resilient = hwa_cfg.resilient
        if is_compressed(tok):
            spec = spec.with_ring_dtype(ring_dtype)
        io = _window_io(mesh, spec, I, ring_dtype)
        names = [n for n, _, _, _ in io]
        has_scales = "scales" in names
        has_comp = "comp" in names
        stacked_pspecs = rules.tree_specs(stacked_abs, stacked_dims)
        # health stats are replicated over every non-replica axis the
        # params are NOT sharded over; psum over the sharded ones and let
        # health_scale cancel the replication overcount (packed.py doc).
        health_axes = tuple(a for a in mesh.axis_names
                            if a not in k_axes and mesh.shape[a] > 1)
        health_scale = math.prod(mesh.shape[a] for a in health_axes) or 1
        body = functools.partial(_local_packed_sync, hwa_cfg,
                                 spec.local_spec(), K, (k_axes,),
                                 hwa_cfg.use_kernels, False,
                                 health_axes=health_axes if resilient else (),
                                 health_scale=health_scale)

        def mesh_sync_step(*args):
            it = iter(args)
            inner, ring = next(it), next(it)
            scales = next(it) if has_scales else None
            total = next(it)
            comp = next(it) if has_comp else None
            count, next_idx = next(it), next(it)
            r = body(inner, ring, total, count, next_idx,
                     jnp.zeros((), jnp.int32), scales, comp)
            out = [r[0], r[1]]
            if has_scales:
                out.append(r[2])
            out.append(r[3])
            if has_comp:
                out.append(r[4])
            out += [r[5], r[6], r[7]]
            if resilient:
                out.append(r[9])
            return tuple(out)

        alive_spec = (P(_axes_entry(k_axes)),) if resilient else ()
        win_pspecs = tuple(p for _, _, p, _ in io)
        step = shard_map(
            mesh_sync_step, mesh,
            in_specs=(stacked_pspecs, *win_pspecs, P(), P()),
            out_specs=(stacked_pspecs, *win_pspecs, P(), P(), pspec_tree,
                       *alive_spec),
            check_rep=False)
        p_sh = rules.tree_shardings(stacked_abs, stacked_dims)
        w_sh = rules.tree_shardings(params_abs, param_dims)
        win_sh = tuple(s for _, _, _, s in io)
        s_sh = NamedSharding(mesh, P())
        alive_sh = (tuple(NamedSharding(mesh, s) for s in alive_spec)
                    if resilient else ())
        k_local = (K // math.prod(mesh.shape[a] for a in k_axes)
                   if k_axes else K)
        budget = packed_sync_launch_budget(
            hwa_cfg, use_kernel=hwa_cfg.use_kernels,
            n_groups=spec.n_groups, k_local=k_local,
            collective=bool(k_axes), with_stride=False, ring_dtype=tok)
        if resilient:
            # two replica-level all-reduces (k_alive, then the masked
            # weight psum — the inv data dependency keeps XLA from
            # merging them) plus one health-stats psum over the
            # non-replica axes when any exist.
            contract = sync_contract(
                k_axes, launches=budget,
                n_collectives=2 if k_axes else 0,
                other_ops={"all-reduce": 1} if health_axes else None,
                float_args=_precision_tokens(tok),
                notes="flat vmap-path sync, mesh-resident, resilient "
                      "(alive-masked mean)")
        else:
            contract = sync_contract(
                k_axes, launches=budget,
                n_collectives=1 if k_axes else 0,
                float_args=_precision_tokens(tok),
                notes="flat vmap-path sync, mesh-resident")
        return StepBundle(
            fn=step,
            abstract_args=(stacked_abs, *(a for _, a, _, _ in io),
                           scalar_i, scalar_i),
            in_shardings=(p_sh, *win_sh, s_sh, s_sh),
            out_shardings=(p_sh, *win_sh, s_sh, s_sh, w_sh, *alive_sh),
            donate_argnums=tuple(range(1 + len(io))), pack_spec=spec,
            contract=contract)

    if hwa_cfg.resilient:
        raise ValueError("resilient HWA requires the mesh-resident packed "
                         "sync path (the legacy GSPMD fallback has no "
                         "alive-masked formulation); use a layout the "
                         "packed chooser accepts or the core hwa_sync")
    check_legacy_assembly(mesh)
    return make_legacy_sync_step(lm, rules, hwa_cfg, ring_dtype, use_kernel)


# ----------------------------------------------- mesh-native HWA (shard_map)
#
# Same storage layout as the vmap path — stacked (K, ...) state with the
# leading dim sharded over the ``replica`` mesh axis (or jointly over the
# ``(pod, replica)`` pair of a two-level topology) — but the step runs
# under shard_map *manual* over those axes (data/model stay auto/GSPMD):
# each replica block squeezes its (1, ...) slice and steps locally, so the
# lowered inner-step HLO provably contains no collective crossing the
# replica axes, and hwa_sync is the topology's psum composition. That
# makes the paper's H-fold inter-replica communication amortization a
# structural property of the program rather than a GSPMD-propagation
# accident.


def _squeeze0(tree):
    return jax.tree.map(lambda x: x[0], tree)


def _expand0(tree):
    return jax.tree.map(lambda x: x[None], tree)


def _make_mesh_hwa_train_step(lm: LM, rules: ShardingRules, batch_specs,
                              batch_dims, hwa_cfg: HWAConfig,
                              optimizer: str = "adamw", lr: float = 3e-4,
                              opt_rules: ShardingRules | None = None,
                              replica_axis: str | tuple[str, ...] = "replica"
                              ) -> StepBundle:
    """Mesh-native inner HWA step.

    Collective-free over ``replica_axis`` by construction (shard_map keeps
    the replica blocks independent; the only collectives GSPMD may insert
    live inside a block, over the data/model axes). ``replica_axis`` may
    name several mesh axes jointly — a two-level topology's ``(pod,
    replica)`` — in which case the step is collective-free over ALL of
    them: the tree changes nothing about the inner step, only about the
    sync. Returns per-replica losses as a (K,) array sharded over the
    replica axes — averaging them to a replicated scalar would itself be
    a replica collective, so the caller takes the mean after fetching.

    With ``lm.cfg.attn_impl == "flash_pallas"`` the step runs under a
    FULLY-manual shard_map instead (every axis manual — Pallas kernels
    are opaque to GSPMD, see the inline comment), with data parallelism
    as an explicit grad pmean and an exact Pallas LaunchBudget
    (1 attention fwd + 2 bwd sweeps per layer) in the contract when
    remat is off.
    """
    from repro.launch.sync.topology import _norm_axes

    opt = _mk_optimizer(optimizer)
    K = hwa_cfg.n_replicas
    mesh = rules.mesh
    rep_axes = _norm_axes(replica_axis)
    rep_entry = rep_axes[0] if len(rep_axes) == 1 else rep_axes
    assert all(a in mesh.shape for a in rep_axes), (rep_axes, mesh.shape)
    rep_size = math.prod(mesh.shape[a] for a in rep_axes)
    assert K == rep_size, \
        f"mesh-native path needs K == replica-axes size ({K} != " \
        f"{rep_size} over {rep_axes}); use the vmap path otherwise"
    auto = frozenset(a for a in mesh.axis_names if a not in rep_axes)
    if not lm.cfg.scan_unroll:
        # XLA (0.4.x) fatals on a while loop under manual-subgroup
        # shardings; unrolling the layer scan keeps the body loop-free.
        from repro.models.registry import build_model
        lm = build_model(lm.cfg.with_(scan_unroll=True))
    params_abs, param_dims = lm.abstract()
    stacked_abs = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((K,) + s.shape, s.dtype), params_abs)
    stacked_dims = _prefix_dims(param_dims, "replica")
    opt_abs = jax.eval_shape(lambda p: jax.vmap(opt.init)(p), stacked_abs)
    o_dims = opt_state_dims(opt_abs, stacked_dims)
    if "count" in o_dims:
        o_dims["count"] = ("replica",)
    opt_rules = opt_rules or rules
    kbatch_abs = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((K,) + s.shape, s.dtype), batch_specs)
    kbatch_dims = _prefix_dims(batch_dims, "replica")

    # The body runs the model's pure-jnp path (rules=None): the rules-aware
    # path opens nested shard_maps (vocab-sharded gather, EP MoE) which 0.4.x
    # cannot nest inside a partial-auto map. Layouts over the auto axes are
    # still driven by the jit in/out shardings; constraints are hints only,
    # so the math is unchanged.
    def loss_fn(params, batch):
        return lm.loss(params, batch, rules=None)

    if lm.cfg.attn_impl == "flash_pallas":
        # Fully-manual variant: a bare pallas_call is OPAQUE to the GSPMD
        # partitioner — under the partial-auto map below XLA would run
        # the attention kernel per-shard with global-shape semantics and
        # silently corrupt values (the same playbook as the mesh-resident
        # sync, launch/sync/packed.py). So the flash-pallas train step
        # goes manual over EVERY mesh axis: the kernel sees true local
        # shapes, data parallelism becomes an explicit grad/loss pmean
        # over the data axes, and the model axis is redundantly
        # replicated (DP-only — TP sharding of the attention kernel is a
        # ROADMAP item). Params/opt live replicated over the non-replica
        # axes at rest, matching the manual specs (no boundary reshard).
        data_axes = tuple(a for a in rules.rules.get("batch", ())
                          if a in mesh.shape and a not in rep_axes)
        data_size = math.prod(mesh.shape[a] for a in data_axes)
        per_rep_b = jax.tree.leaves(batch_specs)[0].shape[0]
        assert not data_axes or per_rep_b % data_size == 0, \
            f"per-replica batch {per_rep_b} must divide over the data " \
            f"axes {data_axes} (size {data_size}) for the fully-manual " \
            f"flash-pallas step"
        data_entry = (data_axes if len(data_axes) > 1
                      else (data_axes[0] if data_axes else None))

        def mesh_train_step(inner, inner_opt, batch):
            params, opt_state = _squeeze0(inner), _squeeze0(inner_opt)
            (loss, _), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, _squeeze0(batch))
            if data_axes:
                grads = jax.lax.pmean(grads, data_axes)
                loss = jax.lax.pmean(loss, data_axes)
            updates, opt_state = opt.update(grads, opt_state, params, lr)
            return (_expand0(apply_updates(params, updates)),
                    _expand0(opt_state), loss[None])

        batch_pspecs = jax.tree.map(
            lambda _: (P(rep_entry, data_entry) if data_entry is not None
                       else P(rep_entry)), kbatch_abs)
        step = shard_map(
            mesh_train_step, mesh,
            in_specs=(stacked_replica_specs(stacked_abs, rep_entry),
                      stacked_replica_specs(opt_abs, rep_entry),
                      batch_pspecs),
            out_specs=(stacked_replica_specs(stacked_abs, rep_entry),
                       stacked_replica_specs(opt_abs, rep_entry),
                       P(rep_entry)),
            check_rep=False)
        to_sh = lambda specs: jax.tree.map(
            lambda s: NamedSharding(mesh, s), specs)
        p_sh = to_sh(stacked_replica_specs(stacked_abs, rep_entry))
        o_sh = to_sh(stacked_replica_specs(opt_abs, rep_entry))
        b_sh = to_sh(batch_pspecs)
        # Structural budget: the layer scan (unroll=True) is ONE jaxpr
        # eqn whose body holds 1 attention fwd + 2 recompute-bwd
        # launches, so the jaxpr count is 3 at any depth; the compiled
        # HLO carries the physical 3 × n_layers custom calls
        # (tests/mesh_hwa_check.py asserts both). Exact only when remat
        # is off (remat re-runs forwards inside the backward).
        launches = 3 if lm.cfg.remat == "none" else None
        return StepBundle(
            fn=step, abstract_args=(stacked_abs, opt_abs, kbatch_abs),
            in_shardings=(p_sh, o_sh, b_sh),
            out_shardings=(p_sh, o_sh, NamedSharding(mesh, P(rep_entry))),
            donate_argnums=(0, 1),
            contract=train_contract(
                replica_axes=rep_axes, launches=launches,
                notes="mesh-native HWA inner step, flash-pallas "
                      "attention (fully-manual, DP over data axes)"))

    def mesh_train_step(inner, inner_opt, batch):
        params, opt_state, loss, _ = hwa_local_inner_step(
            _squeeze0(inner), _squeeze0(inner_opt), _squeeze0(batch),
            loss_fn, opt, lr)
        return _expand0(params), _expand0(opt_state), loss[None]

    step = shard_map(
        mesh_train_step, mesh,
        in_specs=(stacked_replica_specs(stacked_abs, rep_entry),
                  stacked_replica_specs(opt_abs, rep_entry),
                  stacked_replica_specs(kbatch_abs, rep_entry)),
        out_specs=(stacked_replica_specs(stacked_abs, rep_entry),
                   stacked_replica_specs(opt_abs, rep_entry),
                   P(rep_entry)),
        check_rep=False, auto=auto)

    p_sh = rules.tree_shardings(stacked_abs, stacked_dims)
    o_sh = opt_rules.tree_shardings(opt_abs, o_dims)
    b_sh = rules.tree_shardings(kbatch_abs, kbatch_dims)
    losses_sh = NamedSharding(mesh, P(rep_entry))
    return StepBundle(
        fn=step, abstract_args=(stacked_abs, opt_abs, kbatch_abs),
        in_shardings=(p_sh, o_sh, b_sh),
        out_shardings=(p_sh, o_sh, losses_sh),
        donate_argnums=(0, 1),
        # THE amortization claim: zero collectives cross the replica
        # axes in the inner step (checked structurally by hwa-lint)
        contract=train_contract(replica_axes=rep_axes,
                                notes="mesh-native HWA inner step"))


def _mesh_resident_pack(lm, rules, topology):
    """Shared prologue of the mesh-native sync builders: abstract trees,
    the shard-aware packed layout — single-super-axis or grouped, or None
    when even the grouped chooser cannot align the tilings — and the
    sharding trees."""
    params_abs, param_dims = lm.abstract()
    K = math.prod(rules.mesh.shape[a] for a in topology.replica_axes)
    stacked_abs = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((K,) + s.shape, s.dtype), params_abs)
    stacked_dims = _prefix_dims(param_dims, "replica")
    pspec_tree = rules.tree_specs(params_abs, param_dims)
    flat_specs = jax.tree.leaves(pspec_tree)
    flat_shapes = [tuple(l.shape) for l in jax.tree.leaves(params_abs)]
    spec = choose_resident_spec(rules.mesh, params_abs, flat_specs,
                                flat_shapes,
                                exclude=topology.replica_axes)
    return (params_abs, param_dims, stacked_abs, stacked_dims, pspec_tree,
            spec)


def _make_mesh_hwa_sync_step(lm: LM, rules: ShardingRules,
                             hwa_cfg: HWAConfig,
                             ring_dtype=jnp.float32,
                             replica_axis: str = "replica",
                             mesh_resident: bool | None = None,
                             topology: SyncTopology | None = None,
                             comms_dtype: str = "f32") -> StepBundle:
    """Mesh-native synchronization: the once-per-H-steps collective(s).

    **Mesh-resident path (default).** The ENTIRE sync — packed-W̄
    assembly, the weight all-reduce(s), the slide-window push, the W̿
    unpack — runs inside ONE fully-manual ``shard_map`` over every mesh
    axis (``packed._local_packed_sync``). The window state lives in a
    shard-aware packed layout (``packed._mesh_resident_layout`` aligns
    each leaf's tiling with its packed range), so each device assembles
    its own ``(I, P/shards)`` ring slice from its local leaf shards,
    psums the pre-scaled partial mean over the topology's replica axes,
    and runs the window push locally: with ``use_kernels`` that is the
    Pallas kernel on true local shapes, which GSPMD could never be
    trusted with (it runs opaque custom calls per-shard with global-shape
    semantics). tests/mesh_hwa_check.py asserts the structure on the
    lowered HLO via ``launch.hlo.sync_collective_audit``.

    **Topology.** ``topology`` selects WHERE the mean reduces
    (``launch.sync.topology``): ``Flat`` (default; one all-reduce over
    ``replica_axis``) or ``TwoLevel(inner_axis, outer_axis,
    outer_every)``. For ``TwoLevel`` this builder returns the OUTER sync
    bundle — the grouped psum composition (per-pod psum, then the
    cross-pod all-reduce) + window push, bit-identical (0 ULP) to the
    flat K-replica mean for power-of-two pod/member counts — and
    :func:`make_mesh_hwa_inner_sync_step` builds the cheap pod-internal
    restart that runs on the other ``outer_every - 1`` of every
    ``outer_every`` syncs. Audit contract per level: the inner sync's
    single all-reduce crosses ONLY the inner groups; the outer sync adds
    exactly one cross-pod all-reduce on top.

    Going fully manual also sidesteps the XLA 0.4.x partial-auto caveat
    that previously forced the window push OUTSIDE the manual region:
    partial-auto manual subgroups miscompile packed-buffer assembly from
    auto-sharded leaves (a spurious replica-axis reduction doubles the
    values — the same IsManualSubgroup bug class as the scan_unroll item;
    see ROADMAP "partial-auto on new JAX"/"scan under manual subgroups").
    With no auto axes in the sync map there is no subgroup to miscompile.

    **Grouped layouts (FSDP).** Mixed tilings — leaves sharded over
    different axis sets, multi-dim data×model placements included — no
    longer fall back: ``packed.choose_resident_spec`` returns a GROUPED
    ``PackSpec`` whose window state is a PER-GROUP buffer tuple
    (allocate with ``packing.window_buffers(bundle.pack_spec, I)``),
    each group sharded over its own super-axis and pushed by its own
    kernel launch (≤ n_groups pallas_calls), with the weight all-reduce
    still computed ONCE over the concatenated local partials — the audit
    contract (one replica all-reduce, zero assembly collectives) is
    unchanged.

    **Fallback.** The legacy split (``launch.sync.legacy``) survives only
    as an explicitly-requested escape hatch (``mesh_resident=False``) or
    for layouts even the grouped chooser cannot align (zero-size leaves,
    params sharded over replica axes, indivisible tiles): pmean inside a
    partial-auto shard_map, window push outside in GSPMD-land — Flat
    only, one param-size masked all-reduce per sync, and a HARD ERROR on
    multi-device CPU meshes where XLA 0.4.37 miscompiles the assembly
    (``REPRO_ALLOW_LEGACY_ASSEMBLY=1`` downgrades to a warning).
    ``mesh_resident`` forces the choice (True raises if no layout
    qualifies); None picks automatically.

    **pack_spec contract.** Callers allocate the window buffers from
    ``bundle.pack_spec`` — ``ring = zeros((I, spec.padded), ring_dtype)``,
    ``total = zeros((spec.padded,), f32)`` — and read leaf views with
    ``packing.unpack(buf, bundle.pack_spec)``. The mesh-resident layout's
    ``padded`` includes per-segment alignment and replicated-leaf
    duplicates, so it is NOT interchangeable with ``pack_spec(params)``;
    checkpoints written via ``checkpoint.save_window_state`` record the
    layout and repack bit-exactly on load under a different mesh.

    **Donation invariants.** every window-state buffer (stacked inner,
    ring, the fp8 ring's scales, total, the compressed ring's Kahan comp)
    is donated — thread the returned buffers into the next call; the
    scalar counters (count, next_idx, cycle) are returned fresh, not
    donated.

    **Precision.** ``ring_dtype`` compresses the window STORAGE (bf16 or
    block-scaled fp8 ring; f32 total with Kahan compensation — the
    ``scales``/``comp`` args slot in as ``(inner, ring, [scales], total,
    [comp], count, next_idx, cycle)``). ``comms_dtype`` compresses the
    two-level tree's CROSS-POD hop only: the quantized partial is
    all-gathered as a same-width integer bit-view (bf16→u16; fp8→u8
    plus its f32 per-block scales — an fp8 all-reduce would ACCUMULATE
    in fp8) and reduced locally with an f32 halving-sum; the bit-view
    keeps XLA's float normalization from widening the wire payload on
    backends without native narrow-float collectives. The pod-internal
    psum stays f32 either way, so
    the inner tree level keeps its 0-ULP halving composition. Requires a
    TwoLevel topology and is mutually exclusive with ``resilient`` (the
    alive-masked mean renormalizes by k_alive after the psum — the
    quantized payload would be scaled before the mask is known). The f32
    defaults leave both paths bit-identical to the uncompressed bundles.
    """
    from repro.common.quant import is_compressed, wa_dtype, wa_token
    K = hwa_cfg.n_replicas
    I = hwa_cfg.window
    mesh = rules.mesh
    ring_dtype = wa_dtype(ring_dtype)
    tok = wa_token(ring_dtype)
    comms_tok = wa_token(comms_dtype)
    topology = topology if topology is not None else Flat(replica_axis)
    topology.validate(mesh, K)
    if comms_tok != "f32":
        if not isinstance(topology, TwoLevel):
            raise ValueError(
                "compressed comms quantize the two-level tree's cross-pod "
                "hop; a Flat sync has no outer level to compress (its one "
                "all-reduce IS the mean — quantizing it would quantize "
                f"the paper's W̄). Got comms_dtype={comms_tok!r} with "
                f"topology {topology!r}")
        if hwa_cfg.resilient:
            raise ValueError(
                "resilient + compressed comms is unsupported: the "
                "alive-masked mean renormalizes by k_alive after the "
                "psum, so the quantized payload would be scaled before "
                "the mask is known")
    _check_outer_every(hwa_cfg, topology)
    k_axes = _resolved_k_axes(rules, K, topology)
    # Flat keeps the original contract: psum over whatever axes the rules
    # shard the stack over (none → K device-local, collective-free sync).
    # TwoLevel reduces by the topology's inner-then-outer composition.
    psum_groups = (topology.psum_groups()
                   if isinstance(topology, TwoLevel) else (k_axes,))
    scalar_i = jax.ShapeDtypeStruct((), jnp.int32)
    (params_abs, param_dims, stacked_abs, stacked_dims, pspec_tree,
     spec) = _mesh_resident_pack(lm, rules, topology)
    p_sh = rules.tree_shardings(stacked_abs, stacked_dims)
    w_sh = rules.tree_shardings(params_abs, param_dims)
    s_sh = NamedSharding(mesh, P())

    if mesh_resident is None:
        mesh_resident = spec is not None
    elif mesh_resident and spec is None:
        raise ValueError("mesh-resident sync: leaf tilings do not align "
                         "with any packed super-axis or grouped layout")
    if not mesh_resident and isinstance(topology, TwoLevel):
        raise ValueError("the two-level sync tree requires the "
                         "mesh-resident packed path (no legacy GSPMD "
                         "formulation of grouped psums exists)")

    if mesh_resident:
        resilient = hwa_cfg.resilient
        stacked_pspecs = rules.tree_specs(stacked_abs, stacked_dims)
        if is_compressed(tok):
            spec = spec.with_ring_dtype(ring_dtype)
        io = _window_io(mesh, spec, I, ring_dtype)
        names = [n for n, _, _, _ in io]
        has_scales = "scales" in names
        has_comp = "comp" in names
        rep_axes = tuple(topology.replica_axes)
        health_axes = tuple(a for a in mesh.axis_names
                            if a not in rep_axes and mesh.shape[a] > 1)
        health_scale = math.prod(mesh.shape[a] for a in health_axes) or 1
        body = functools.partial(_local_packed_sync, hwa_cfg,
                                 spec.local_spec(), K, psum_groups,
                                 hwa_cfg.use_kernels, True,
                                 comms_dtype=comms_tok,
                                 health_axes=health_axes if resilient else (),
                                 health_scale=health_scale)

        def mesh_sync_step(*args):
            it = iter(args)
            inner, ring = next(it), next(it)
            scales = next(it) if has_scales else None
            total = next(it)
            comp = next(it) if has_comp else None
            count, next_idx, cycle = next(it), next(it), next(it)
            r = body(inner, ring, total, count, next_idx, cycle,
                     scales, comp)
            out = [r[0], r[1]]
            if has_scales:
                out.append(r[2])
            out.append(r[3])
            if has_comp:
                out.append(r[4])
            out += [r[5], r[6], r[7], r[8]]
            if resilient:
                out.append(r[9])
            return tuple(out)

        alive_spec = (P(_axes_entry(k_axes)),) if resilient else ()
        win_pspecs = tuple(p for _, _, p, _ in io)
        step = shard_map(
            mesh_sync_step, mesh,
            in_specs=(stacked_pspecs, *win_pspecs, P(), P(), P()),
            out_specs=(stacked_pspecs, *win_pspecs, P(), P(), pspec_tree,
                       P(), *alive_spec),
            check_rep=False)
        win_sh = tuple(s for _, _, _, s in io)
        alive_sh = (tuple(NamedSharding(mesh, s) for s in alive_spec)
                    if resilient else ())
        psum_axes = tuple(a for g in psum_groups for a in g)
        k_local = (K // math.prod(mesh.shape[a] for a in psum_axes)
                   if psum_axes else K)
        budget = packed_sync_launch_budget(
            hwa_cfg, use_kernel=hwa_cfg.use_kernels,
            n_groups=spec.n_groups, k_local=k_local,
            collective=any(psum_groups), with_stride=True,
            ring_dtype=tok)
        float_args = _precision_tokens(tok)
        coll_dtypes = _precision_tokens(comms_tok)
        if comms_tok != "f32":
            # The compressed cross-pod payload crosses the wire as a
            # same-width integer bit-view (bf16→u16, e4m3fn→u8): XLA's
            # float-normalization pass on backends without native
            # narrow-float collectives (CPU included) would otherwise
            # widen the payload back (bf16 all-reduce → f32 promotion,
            # fp8 gather → f16), silently restoring the full wire bytes.
            coll_dtypes = coll_dtypes + (
                "u16" if comms_tok == "bf16" else "u8",)
        # Resilient doubles each level's replica collectives: k_alive
        # first, then the masked weight psum (the inv dependency chains
        # them so the AllReduceCombiner cannot merge); the health-stats
        # psum crosses only the non-replica axes and is budgeted as an
        # `other_ops` exception rather than loosening the level counts.
        other = ({"all-reduce": 1} if (resilient and health_axes)
                 else None)
        if isinstance(topology, TwoLevel):
            # Compressed comms replace the outer all-reduce with
            # all-gathers + a local f32 halving-sum: one u16 gather for
            # bf16, a u8 payload + f32 per-block scales pair for fp8.
            outer_ops = ({"all-gather": 2} if comms_tok == "fp8" else
                         {"all-gather": 1} if comms_tok == "bf16" else
                         {"all-reduce": 2 if resilient else 1})
            contract = sync_contract(
                topology.inner_axis, launches=budget,
                outer_axis=topology.outer_axis,
                n_collectives=2 if resilient else 1,
                outer_ops=outer_ops,
                other_ops=other,
                collective_dtypes=coll_dtypes,
                float_args=float_args,
                notes="two-level outer sync: per-pod psum + cross-pod "
                      + ("fp8 all-gather pair" if comms_tok == "fp8"
                         else "bf16 (u16 bit-view) all-gather"
                         if comms_tok == "bf16" else "all-reduce")
                      + (", resilient (alive-masked)" if resilient else ""))
        else:
            contract = sync_contract(
                k_axes, launches=budget,
                n_collectives=(2 if resilient else 1) if k_axes else 0,
                other_ops=other,
                float_args=float_args,
                notes="mesh-native flat sync, mesh-resident"
                      + (", resilient (alive-masked)" if resilient else ""))
        return StepBundle(
            fn=step,
            abstract_args=(stacked_abs, *(a for _, a, _, _ in io),
                           scalar_i, scalar_i, scalar_i),
            in_shardings=(p_sh, *win_sh, s_sh, s_sh, s_sh),
            out_shardings=(p_sh, *win_sh, s_sh, s_sh, w_sh, s_sh,
                           *alive_sh),
            donate_argnums=tuple(range(1 + len(io))), pack_spec=spec,
            contract=contract)

    # ------- legacy fallback: partial-auto pmean + GSPMD-land window push
    if hwa_cfg.resilient:
        raise ValueError("resilient HWA requires the mesh-resident packed "
                         "sync path (the legacy GSPMD fallback has no "
                         "alive-masked formulation)")
    if len(topology.replica_axes) != 1:
        raise ValueError("the legacy GSPMD fallback handles a single "
                         f"replica axis only, got {topology.replica_axes}")
    check_legacy_assembly(mesh)
    return make_legacy_mesh_sync_step(lm, rules, hwa_cfg, ring_dtype,
                                      topology.replica_axes[0])


def _make_mesh_hwa_inner_sync_step(lm: LM, rules: ShardingRules,
                                   hwa_cfg: HWAConfig,
                                   topology: TwoLevel) -> StepBundle:
    """The two-level tree's INNER sync: pod-internal averaging + restart.

    Runs on the ``outer_every - 1`` of every ``outer_every`` syncs that
    are NOT outer (``topology.is_outer``). Each pod pmeans over its OWN
    members — one all-reduce whose explicit ``replica_groups`` pair only
    same-pod devices, zero cross-pod traffic, zero window-state traffic
    (the slide window collects global W̄ only, so ring/total/counters are
    untouched and are not even arguments here). Signature is simply
    stacked-inner → stacked-inner, with the input donated.

    Mesh-resident only: the pod mean is assembled/unpacked through the
    same shard-aware packed layout as the outer sync (one collective
    total); tilings that do not align raise, like the forced
    mesh-resident outer path.
    """
    K = hwa_cfg.n_replicas
    mesh = rules.mesh
    if not isinstance(topology, TwoLevel):
        raise ValueError("inner-only sync exists only for the TwoLevel "
                         f"topology, got {topology!r}")
    topology.validate(mesh, K)
    _check_outer_every(hwa_cfg, topology)
    _resolved_k_axes(rules, K, topology)
    (params_abs, param_dims, stacked_abs, stacked_dims, pspec_tree,
     spec) = _mesh_resident_pack(lm, rules, topology)
    if spec is None:
        raise ValueError("inner sync: leaf tilings do not align with any "
                         "packed super-axis or grouped layout "
                         "(mesh-resident only)")
    stacked_pspecs = rules.tree_specs(stacked_abs, stacked_dims)
    pod_size = K // topology.pods(mesh)
    lspec, groups = spec.local_spec(), topology.inner_groups()

    def mesh_inner_sync_step(inner):
        return _local_inner_sync(lspec, pod_size, groups, inner)

    step = shard_map(
        mesh_inner_sync_step, mesh,
        in_specs=(stacked_pspecs,),
        out_specs=stacked_pspecs,
        check_rep=False)
    p_sh = rules.tree_shardings(stacked_abs, stacked_dims)
    return StepBundle(
        fn=step, abstract_args=(stacked_abs,),
        in_shardings=(p_sh,), out_shardings=p_sh,
        donate_argnums=(0,), pack_spec=spec,
        contract=sync_contract(
            topology.inner_axis, launches=0,
            outer_axis=topology.outer_axis,
            n_collectives=1, outer_collectives=0,
            notes="two-level inner sync: one per-pod all-reduce, zero "
                  "cross-pod traffic, zero kernel launches"))


# ------------------------------------------------- deprecated flat names
#
# PR 10 collapsed the five HWA builders behind ONE declarative entry
# point: construct a ``launch.sync.plan.SyncPlan`` (topology × precision
# × resilience × kernels) and call ``build_hwa_bundles(lm, rules, plan)``.
# The historical names survive as thin wrappers so pre-plan callers keep
# working; they carry no logic of their own and will be removed once the
# last in-repo caller migrates.


def _deprecated(name: str) -> None:
    warnings.warn(
        f"{name} is deprecated: describe the configuration with a "
        "repro.launch.sync.plan.SyncPlan and call build_hwa_bundles "
        "instead", DeprecationWarning, stacklevel=3)


def make_hwa_train_step(*args, **kwargs) -> StepBundle:
    """Deprecated name for the vmap-path inner step (use
    ``plan.build_hwa_bundles``)."""
    _deprecated("make_hwa_train_step")
    return _make_hwa_train_step(*args, **kwargs)


def make_hwa_sync_step(*args, **kwargs) -> StepBundle:
    """Deprecated name for the flat stacked sync (use
    ``plan.build_hwa_bundles``)."""
    _deprecated("make_hwa_sync_step")
    return _make_hwa_sync_step(*args, **kwargs)


def make_mesh_hwa_train_step(*args, **kwargs) -> StepBundle:
    """Deprecated name for the mesh-native inner step (use
    ``plan.build_hwa_bundles``)."""
    _deprecated("make_mesh_hwa_train_step")
    return _make_mesh_hwa_train_step(*args, **kwargs)


def make_mesh_hwa_sync_step(*args, **kwargs) -> StepBundle:
    """Deprecated name for the mesh-native sync (use
    ``plan.build_hwa_bundles``)."""
    _deprecated("make_mesh_hwa_sync_step")
    return _make_mesh_hwa_sync_step(*args, **kwargs)


def make_mesh_hwa_inner_sync_step(*args, **kwargs) -> StepBundle:
    """Deprecated name for the two-level inner sync (use
    ``plan.build_hwa_bundles``)."""
    _deprecated("make_mesh_hwa_inner_sync_step")
    return _make_mesh_hwa_inner_sync_step(*args, **kwargs)
