"""Kernel micro-benchmarks.

Wall-times are the jit'd XLA *reference* implementations on CPU (the
Pallas kernels run in interpret mode here — TPU is the target, so their
value is the HBM-traffic model, reported as derived columns):

  fused wa_window_update : 3 reads + 3 writes vs naive 6 reads + 3 writes
  fused sync             : (K+2) reads + 3 writes vs (K+3) reads + 4 writes
  online_mean            : K reads + 1 write (fused cast)

The packed-vs-per-leaf comparison drives a transformer-like tree
(≥100 leaves, mixed 128-element biases and 1M-element matrices) through
both WA-update formulations and reports, per path: kernel-launch count
(structural, from the jaxpr), padding waste (bytes padded / bytes
useful), and ref-impl wall time.

The gated-vs-mesh-resident comparison (subprocess, 8 forced host
devices, (2,2,2) replica/data/model mesh) lowers the mesh sync bundle
both ways and reports, per path: Pallas launches, collective counts and
modeled per-device ICI bytes per sync split into the replica-axis weight
all-reduce vs packed-W̄ assembly traffic — the cost the shard-aware
layout removes. ``benchmarks.run`` tees the returned dict into
BENCH_kernels.json at the repo root for cross-PR tracking.
"""
import json
import os
import sys
import time

import jax
import jax.numpy as jnp

# CI bench-smoke lane: shrink the buffers/tree/attention so the suite
# runs in seconds while every STRUCTURAL metric (launch counts,
# collective counts, padding-waste order) keeps the same contract —
# tools/bench_check.py guards exactly those, never wall times.
SMOKE = os.environ.get("REPRO_BENCH_SMOKE") == "1"

from repro.common.packing import ALIGN, pack, pack_spec, pack_stacked
from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.launch.hlo import count_pallas_calls
from benchmarks.common import csv_row


def _time(fn, *args, iters=20):
    jax.block_until_ready(fn(*args))     # warm up with ONE call
    t0 = time.time()
    for _ in range(iters):
        jax.block_until_ready(fn(*args))
    return (time.time() - t0) / iters * 1e6


def transformer_like_tree(key=0):
    """≥100 leaves with a transformer's size mix: a few 1M-element
    matrices, mid-size projections, and many 128-element biases (the
    SMOKE lane keeps the mix but shrinks every class)."""
    ks = iter(jax.random.split(jax.random.key(key), 128))
    n_embed, embed_shape, n_proj, n_bias = \
        (2, (256, 512), 10, 20) if SMOKE else (2, (1024, 1024), 30, 70)
    tree = {}
    for i in range(n_embed):
        tree[f"embed_{i}"] = jax.random.normal(next(ks), embed_shape)
    for i in range(n_proj):
        tree[f"proj_{i}"] = jax.random.normal(next(ks), (128, 512))
    for i in range(n_bias):
        tree[f"bias_{i}"] = jax.random.normal(next(ks), (128,))
    return tree


def _per_leaf_pad_waste(tree):
    useful = padded = 0
    for leaf in jax.tree.leaves(tree):
        n = leaf.size
        useful += n
        padded += -(-n // ALIGN) * ALIGN
    return (padded - useful) / useful


def packed_vs_per_leaf(print_fn=print):
    I, K = 4, 2
    tree = transformer_like_tree()
    n_leaves = len(jax.tree.leaves(tree))
    spec = pack_spec(tree)

    # --- launch counts (structural: pallas_call eqns in the jaxpr) ------
    def per_leaf_update(ring, total, new):
        triples = jax.tree.map(
            lambda r, t, n: kops.wa_window_update(r, t, n, 0, 1.0, 1.0 / I),
            ring, total, new)
        is3 = lambda x: isinstance(x, tuple) and len(x) == 3
        return jax.tree.map(lambda t: t[1], triples, is_leaf=is3)

    ring_tree = jax.tree.map(lambda x: jnp.zeros((I,) + x.shape), tree)
    total_tree = jax.tree.map(jnp.zeros_like, tree)
    launches_per_leaf = count_pallas_calls(jax.make_jaxpr(per_leaf_update)(
        ring_tree, total_tree, tree))

    ring = jnp.zeros((I, spec.padded))
    total = jnp.zeros((spec.padded,))
    new = pack(tree, spec)
    launches_packed = count_pallas_calls(jax.make_jaxpr(
        lambda r, t, n: kops.wa_window_update_packed(r, t, n, 0, 1.0, 1.0 / I)
    )(ring, total, new))
    stacked = jnp.stack([new, new])
    launches_fused = count_pallas_calls(jax.make_jaxpr(
        lambda s, r, t: kops.hwa_sync_packed(s, r, t, 0, 1.0, 1.0 / I)
    )(stacked, ring, total))

    # --- padding waste --------------------------------------------------
    waste_per_leaf = _per_leaf_pad_waste(tree)
    waste_packed = spec.pad_waste

    # --- wall time: donated steady-state loop (state threaded through,
    # ring/total updated in place — the deployment shape), jit'd refs.
    # On CPU the elementwise work dominates and XLA fuses either way; the
    # launch-count/padding columns above are the TPU-side story.
    idx = jnp.zeros((), jnp.int32)

    def _time_threaded(fn, ring, total, new, iters=10):
        ring, total, avg = fn(ring, total, new)
        jax.block_until_ready((ring, total, avg))
        t0 = time.time()
        for _ in range(iters):
            ring, total, avg = fn(ring, total, new)
            jax.block_until_ready(avg)
        jax.block_until_ready((ring, total))
        return (time.time() - t0) / iters * 1e6

    def leaf_ref(ring, total, new):
        # keep all of (ring', total', avg): dropping any lets XLA DCE
        # that part of the update and skews the timing
        triples = jax.tree.map(
            lambda r, t, n: kref.wa_window_update_ref(r, t, n, idx, 1.0,
                                                      1.0 / I),
            ring, total, new)
        is3 = lambda x: isinstance(x, tuple) and len(x) == 3
        pick = lambda i: jax.tree.map(lambda t: t[i], triples, is_leaf=is3)
        return pick(0), pick(1), pick(2)

    us_leaf = _time_threaded(jax.jit(leaf_ref, donate_argnums=(0, 1)),
                             ring_tree, total_tree, tree)
    packed_ref = jax.jit(lambda r, t, n: kref.wa_window_update_ref(
        r, t, n, idx, 1.0, 1.0 / I), donate_argnums=(0, 1))
    us_packed = _time_threaded(packed_ref, ring, total, new)
    fused_ref = jax.jit(lambda s, r, t: kref.wa_sync_fused_ref(
        s, r, t, idx, 1.0, 1.0 / I), donate_argnums=(1, 2))
    ring2 = jnp.zeros((I, spec.padded))     # previous buffers were donated
    total2 = jnp.zeros((spec.padded,))
    us_fused = _time_threaded(
        lambda r, t, n: fused_ref(stacked, r, t), ring2, total2, new)

    useful_bytes = 4 * spec.size
    rec = {
        "n_leaves": n_leaves, "window": I, "n_replicas": K,
        "useful_bytes": useful_bytes,
        "launches_per_leaf": launches_per_leaf,
        "launches_packed": launches_packed,
        "launches_fused_sync": launches_fused,
        "pad_waste_per_leaf": waste_per_leaf,
        "pad_waste_packed": waste_packed,
        "us_per_leaf_ref": us_leaf, "us_packed_ref": us_packed,
        "us_fused_sync_ref": us_fused,
    }
    print_fn(csv_row(
        "kernel/packed_vs_per_leaf/launches", 0.0,
        f"leaves={n_leaves};per_leaf={launches_per_leaf};"
        f"packed={launches_packed};fused_sync={launches_fused}"))
    print_fn(csv_row(
        "kernel/packed_vs_per_leaf/pad_waste", 0.0,
        f"per_leaf={waste_per_leaf:.4f};packed={waste_packed:.6f}"))
    print_fn(csv_row("kernel/wa_window_update_per_leaf_ref", us_leaf,
                     f"leaves={n_leaves};bytes={useful_bytes}"))
    print_fn(csv_row("kernel/wa_window_update_packed_ref", us_packed,
                     f"leaves={n_leaves};bytes={useful_bytes}"))
    print_fn(csv_row("kernel/hwa_sync_fused_ref", us_fused,
                     f"K={K};bytes={useful_bytes}"))
    return rec


_WORKER_FLAG = "--mesh-sync-worker"


def _mesh_sync_worker():
    """Runs with 8 forced host devices: lower the mesh sync bundle gated
    (legacy GSPMD fallback) vs mesh-resident and measure the difference."""
    import os

    import jax

    # The gated leg deliberately builds the legacy GSPMD fallback, which
    # is a hard error on multi-device CPU meshes (launch/sync/legacy.py).
    # This worker only introspects the lowered HLO and never trusts
    # computed values, so opt into the escape hatch.
    os.environ.setdefault("REPRO_ALLOW_LEGACY_ASSEMBLY", "1")

    from repro.configs import get_smoke_config
    from repro.core.hwa import HWAConfig
    from repro.launch.hlo import (collective_stats, count_pallas_calls,
                                  result_bytes, sync_collective_audit)
    from repro.launch.mesh import make_test_mesh
    from repro.launch.steps import make_mesh_hwa_sync_step
    from repro.models.registry import build_model
    from repro.sharding.rules import make_tp_rules

    mesh = make_test_mesh((2, 2, 2), ("replica", "data", "model"))
    rules = make_tp_rules(mesh, replica_axis="replica")
    rules_fsdp = make_tp_rules(mesh, replica_axis="replica", fsdp=True)
    lm = build_model(get_smoke_config("granite-3-2b"))
    out = {}
    # fsdp_grouped: the FSDP mixed data×model tilings through the GROUPED
    # mesh-resident layout — per-group launches (≤ n_groups), still zero
    # assembly collectives (before the grouped chooser this tree was
    # stuck on the legacy path measured by the "gated" leg)
    for name, leg_rules, resident in [("gated", rules, False),
                                      ("mesh_resident", rules, True),
                                      ("fsdp_grouped", rules_fsdp, True)]:
        hwa_cfg = HWAConfig(n_replicas=2, window=3, use_kernels=True)
        bundle = make_mesh_hwa_sync_step(lm, leg_rules, hwa_cfg,
                                         mesh_resident=resident)
        compiled = bundle.lower(mesh).compile()
        hlo = compiled.as_text()
        audit = sync_collective_audit(hlo, mesh)
        assembly = {h for hits in audit["other"].values() for h in hits}
        out[name] = {
            "pallas_launches": count_pallas_calls(
                jax.make_jaxpr(bundle.fn)(*bundle.abstract_args)),
            "collectives": sum(collective_stats(hlo).counts.values()),
            "replica_allreduce_bytes": result_bytes(audit["replica"]),
            "assembly_collectives": len(assembly),
            "assembly_bytes": result_bytes(sorted(assembly)),
            "ici_bytes_per_sync": collective_stats(hlo).traffic_bytes,
            "pack_padded_bytes": 4 * bundle.pack_spec.padded,
            "n_groups": bundle.pack_spec.n_groups,
        }
    print(json.dumps(out))


def gated_vs_mesh_resident(print_fn=print):
    """Subprocess driver (forced host devices must not leak into the
    benchmark process)."""
    from benchmarks.common import run_forced_device_worker
    rec = run_forced_device_worker(__file__, _WORKER_FLAG,
                                   error_row="kernel/mesh_sync/ERROR",
                                   print_fn=print_fn)
    for name in ("gated", "mesh_resident", "fsdp_grouped"):
        r = rec[name]
        print_fn(csv_row(
            f"kernel/mesh_sync/{name}", 0.0,
            f"launches={r['pallas_launches']};"
            f"n_groups={r['n_groups']};"
            f"collectives={r['collectives']};"
            f"assembly_collectives={r['assembly_collectives']};"
            f"assembly_bytes={r['assembly_bytes']};"
            f"weight_allreduce_bytes={r['replica_allreduce_bytes']};"
            f"ici_bytes_per_sync={r['ici_bytes_per_sync']:.3e}"))
    return rec


def main(print_fn=print):
    out = {}
    N = 1 << 15 if SMOKE else 1 << 20
    I, K = 8, 4
    ring = jnp.zeros((I, N), jnp.float32)
    total = jnp.zeros((N,), jnp.float32)
    new = jnp.ones((N,), jnp.float32)

    ref = jax.jit(lambda r, t, n: kref.wa_window_update_ref(
        r, t, n, 3, 1.0, 1.0 / I))
    us = _time(ref, ring, total, new)
    naive_bytes = (6 * N + 3 * N) * 4
    fused_bytes = (3 * N + 3 * N) * 4
    out["wa_window_update"] = {"us": us, "bytes_naive": naive_bytes,
                               "bytes_fused": fused_bytes}
    print_fn(csv_row("kernel/wa_window_update", us,
                     f"bytes_naive={naive_bytes};bytes_fused={fused_bytes};"
                     f"traffic_cut={1 - fused_bytes / naive_bytes:.2f}"))

    stacked = jnp.ones((K, N), jnp.float32)
    ref2 = jax.jit(kref.online_mean_ref)
    us = _time(ref2, stacked)
    out["online_mean"] = {"us": us, "bytes": (K * N + N) * 4}
    print_fn(csv_row("kernel/online_mean", us,
                     f"bytes={(K * N + N) * 4}"))

    # fused sync: (K+2) reads + 3 writes vs two kernels' (K+3) + 4
    ref3 = jax.jit(lambda s, r, t: kref.wa_sync_fused_ref(
        s, r, t, 3, 1.0, 1.0 / I))
    us = _time(ref3, stacked, ring, total)
    sync_fused_bytes = ((K + 2) * N + 3 * N) * 4
    sync_split_bytes = ((K + 3) * N + 4 * N) * 4
    out["wa_sync_fused"] = {"us": us, "bytes_fused": sync_fused_bytes,
                            "bytes_two_kernel": sync_split_bytes}
    print_fn(csv_row("kernel/wa_sync_fused", us,
                     f"bytes_fused={sync_fused_bytes};"
                     f"bytes_two_kernel={sync_split_bytes};"
                     f"traffic_cut={1 - sync_fused_bytes / sync_split_bytes:.2f}"))

    out["packed_vs_per_leaf"] = packed_vs_per_leaf(print_fn)
    out["mesh_sync_gated_vs_resident"] = gated_vs_mesh_resident(print_fn)
    out.update(attention_suite(print_fn))
    return out


def attention_suite(print_fn=print):
    """Attention fwd + bwd + train-step blocks.

    Forward wall times compare the XLA implementations (naive O(S^2) ref
    vs blockwise flash_jnp) at full size; the Pallas pipeline runs in
    interpret mode on CPU, so its wall time is measured at a CAPPED size
    (a tracer-speed number, not a kernel speed — TPU is the target) and
    its real contract here is STRUCTURAL: exactly 1 forward launch and 2
    recompute-backward sweep launches (dq k-innermost; dk/dv q-innermost)
    under ``jax.grad``, guarded by thresholds.json. The train-step block
    times one jitted value_and_grad+SGD step of the smoke model with
    flash_pallas vs flash_jnp attention and pins the same 3-launch
    budget through the model's layer scan (structural: the scan body
    traces once, so the jaxpr count is depth-independent)."""
    from repro.kernels.flash_attention import flash_attention_pallas
    from repro.models.attention import flash_attention_jnp

    out = {}
    B, S, H, D = (2, 256, 4, 64) if SMOKE else (2, 1024, 4, 64)
    ks = jax.random.split(jax.random.key(0), 4)
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, H, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, H, D), jnp.float32)
    naive = jax.jit(lambda q, k, v: kref.attention_ref(q, k, v))
    us_naive = _time(naive, q, k, v, iters=5)
    flash = jax.jit(lambda q, k, v: flash_attention_jnp(q, k, v))
    us_flash = _time(flash, q, k, v, iters=5)
    out["attention_naive_ref"] = {"us": us_naive}
    out["attention_flash_jnp"] = {"us": us_flash}
    print_fn(csv_row("kernel/attention_naive_ref", us_naive,
                     f"S={S};mem=O(S^2)"))
    print_fn(csv_row("kernel/attention_flash_jnp", us_flash,
                     f"S={S};mem=O(S*block)"))

    # --- backward: jax.grad wall times at full size (XLA refs) ---------
    w = jax.random.normal(ks[3], (B, S, H, D), jnp.float32)
    g_naive = jax.jit(jax.grad(
        lambda q, k, v: jnp.sum(kref.attention_ref(q, k, v) * w),
        (0, 1, 2)))
    us_naive_bwd = _time(g_naive, q, k, v, iters=5)
    g_flash = jax.jit(jax.grad(
        lambda q, k, v: jnp.sum(flash_attention_jnp(q, k, v) * w),
        (0, 1, 2)))
    us_flash_bwd = _time(g_flash, q, k, v, iters=5)

    # Pallas custom-vjp leg, capped (interpret mode pays tracer overhead
    # per block — the structural launch counts are the portable claim)
    Sp, Hkv = 128, 2
    kp = jax.random.split(jax.random.key(1), 4)
    qs = jax.random.normal(kp[0], (B, Sp, H, D), jnp.float32)
    ks_ = jax.random.normal(kp[1], (B, Sp, Hkv, D), jnp.float32)
    vs = jax.random.normal(kp[2], (B, Sp, Hkv, D), jnp.float32)
    ws = jax.random.normal(kp[3], (B, Sp, H, D), jnp.float32)

    def fwd(q, k, v):
        return flash_attention_pallas(q, k, v, block_q=64, block_k=64,
                                      interpret=True)

    g_pallas = jax.jit(jax.grad(
        lambda q, k, v: jnp.sum(fwd(q, k, v) * ws), (0, 1, 2)))
    us_pallas_bwd = _time(g_pallas, qs, ks_, vs, iters=3)

    fwd_launches = count_pallas_calls(
        jax.make_jaxpr(fwd)(qs, ks_, vs))
    fwd_bwd_launches = count_pallas_calls(jax.make_jaxpr(jax.grad(
        lambda q, k, v: jnp.sum(fwd(q, k, v) * ws), (0, 1, 2)))(
            qs, ks_, vs))
    out["attention_bwd"] = {
        "us_naive_ref": us_naive_bwd,
        "us_flash_jnp": us_flash_bwd,
        "us_pallas_interp": us_pallas_bwd,
        "S": S, "S_pallas_interp": Sp,
        "fwd_launches": fwd_launches,
        "bwd_launches": fwd_bwd_launches - fwd_launches,
        "fwd_bwd_launches": fwd_bwd_launches,
    }
    print_fn(csv_row(
        "kernel/attention_bwd", us_flash_bwd,
        f"S={S};naive_us={us_naive_bwd:.0f};"
        f"pallas_interp_us@S{Sp}={us_pallas_bwd:.0f};"
        f"fwd_launches={fwd_launches};"
        f"bwd_launches={fwd_bwd_launches - fwd_launches}"))

    # --- train step: the smoke model end-to-end, both attention paths --
    from repro.configs import get_smoke_config
    from repro.launch.specs import input_specs
    from repro.models.registry import build_model
    from repro.models.types import InputShape

    cfg = get_smoke_config("granite-3-2b")
    shape = InputShape("tiny", seq_len=16, global_batch=2, kind="train")
    specs, _ = input_specs(cfg, shape)
    batch = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), specs)
    rec = {}
    for impl in ("flash_jnp", "flash_pallas"):
        lm = build_model(cfg.with_(attn_impl=impl))
        params = lm.init(jax.random.key(0))

        def step(p, b, lm=lm):
            (loss, _), grads = jax.value_and_grad(
                lm.loss, has_aux=True)(p, b)
            return jax.tree.map(lambda x, g: x - 0.01 * g, p, grads), loss

        rec[f"{impl}_us"] = _time(jax.jit(step), params, batch, iters=3)
        if impl == "flash_pallas":
            rec["flash_pallas_structural_launches"] = count_pallas_calls(
                jax.make_jaxpr(step)(params, batch))
            rec["n_layers"] = lm.cfg.n_layers
    out["attention_train_step"] = rec
    print_fn(csv_row(
        "kernel/attention_train_step", rec["flash_pallas_us"],
        f"flash_jnp_us={rec['flash_jnp_us']:.0f};"
        f"structural_launches={rec['flash_pallas_structural_launches']};"
        f"n_layers={rec['n_layers']}"))
    return out


if __name__ == "__main__":
    if _WORKER_FLAG in sys.argv:
        _mesh_sync_worker()
    elif "--attn-only" in sys.argv:
        # print-only lane (`make bench-attn`): benchmarks.run owns
        # BENCH_kernels.json merging; a partial dict would drop the other
        # kernel blocks, so this path never writes JSON
        attention_suite()
    else:
        main()
