"""Executed in a subprocess with 8 forced host devices (see
test_mesh_hwa.py).

Verifies the tentpole properties of mesh-native HWA on a (2,2,2)
(replica, data, model) mesh:

  1. mesh-native train step == vmap-path train step == single-device
     oracle, within 1e-5 after several steps (f32 smoke model);
  2. mesh-native sync == stacked-mean oracle; replicas restart equal;
     the slide window advances;
  3. the lowered inner train step contains NO collective crossing the
     replica mesh axis — inter-replica traffic happens only in hwa_sync
     (every H steps), which is the paper's communication amortization;
  4. the mesh-RESIDENT sync (shard-aware packed layout, fully-manual
     shard_map) is bit-identical to the single-device fused Pallas path
     AND to the per-leaf reference, compiles to exactly ONE Pallas launch
     per sync, and its HLO contains exactly one replica-axis all-reduce
     and ZERO collectives crossing any other axis (collective-free
     packed-W̄ assembly);
  5. the TWO-LEVEL sync tree (pod-carved (pod=2, replica=2, model=2)
     mesh, K=4) is bit-identical — 0 ULP — to the flat path and to the
     per-leaf grouped reference; its lowered HLO passes the per-level
     sync_collective_audit (inner sync: one per-pod all-reduce, zero
     cross-pod; outer sync: exactly one cross-pod all-reduce on top);
     the tuple-axis train step is collective-free over pod AND replica;
     and the legacy GSPMD fallback is a hard error on this CPU mesh
     unless REPRO_ALLOW_LEGACY_ASSEMBLY=1;
  6. FSDP mixed tilings (fsdp=True rules: data-only, model-only AND
     data×model leaves at once) sync through the GROUPED mesh-resident
     layout — per-group window-buffer tuples, ≤ n_groups Pallas
     launches, still exactly one replica all-reduce and zero assembly
     collectives — bit-identical to the per-leaf reference, with no
     legacy-assembly error;
  7. COMPRESSED WA precision (PR 10): the bf16-ring flat kernel sync and
     the fp8-ring + fp8-comms tree sync stay within the per-dtype
     relative-ULP budgets of benchmarks/thresholds.json (the same
     numbers bench-check guards) against the exact-f32 legs above; the
     fp8 tree's cross-pod hop compiles to the u8-payload + f32-scales
     all-gather pair (the integer bit-view XLA cannot widen).

All oracles are computed on HOST-materialized copies, so no oracle
shares a partitioner pattern with the code it checks: eagerly packing
DISTRIBUTED leaves (a concat across differently-sharded operands) was
once miscompiled by XLA's CPU SPMD partitioner, replicated shards
overcounted ~(data×model)-fold. The legacy GSPMD sync path hit the same
pattern in-jit; the mesh-resident layout assembles shard-locally and
leaves nothing for the partitioner to get wrong (the legacy fallback is
asserted, structurally only, below).
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.packing import pack_spec, pack_stacked, unpack
from repro.configs import get_smoke_config
from repro.core.hwa import HWAConfig
from repro.core.offline import window_init, window_update
from repro.launch.hlo import (collectives_crossing_axis, count_pallas_calls,
                              sync_collective_audit)
from repro.launch.mesh import make_test_mesh
from repro.launch.specs import input_specs
from repro.launch.steps import SyncPlan, build_hwa_bundles
from repro.models.registry import build_model
from repro.models.types import InputShape
from repro.optim import apply_updates, sgd
from repro.sharding.rules import make_tp_rules

ok = True
K, B, S, N_STEPS, LR = 2, 8, 16, 3, 0.1


def check(name, cond):
    global ok
    print(("PASS " if cond else "FAIL ") + name)
    ok = ok and cond


def to_host(tree):
    """Host copies — oracle math must never run on distributed arrays
    (see module docstring)."""
    return jax.tree.map(lambda x: jnp.asarray(np.asarray(x)), tree)


def tree_err(a, b):
    return max(float(np.max(np.abs(np.asarray(x, np.float32)
                                   - np.asarray(y, np.float32))))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def tree_equal(a, b):
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


mesh = make_test_mesh((2, 2, 2), ("replica", "data", "model"))
rules = make_tp_rules(mesh, replica_axis="replica")
cfg = get_smoke_config("granite-3-2b")
lm = build_model(cfg)
hwa_cfg = HWAConfig(n_replicas=K, window=3)
shape = InputShape("tiny", seq_len=S, global_batch=B, kind="train")
specs, dims = input_specs(cfg, shape)

params = lm.init(jax.random.key(0))
stack2 = lambda t: jax.tree.map(lambda x: jnp.stack([x, x]), t)
opt = sgd(momentum=0.9, weight_decay=5e-4)


def batches(step):
    ks = jax.random.split(jax.random.key(100 + step), 2)
    return {"tokens": jax.random.randint(ks[0], (K, B, S), 0,
                                         cfg.vocab_size),
            "targets": jax.random.randint(ks[1], (K, B, S), 0,
                                          cfg.vocab_size)}


# every bundle in this file comes from the ONE declarative constructor
# (PR 10); the old make_*hwa*_step names are deprecated wrappers
def mk_train(lm_, rules_, hwa, **kw):
    plan = SyncPlan(hwa=hwa, optimizer="sgd", lr=LR, **kw)
    return build_hwa_bundles(lm_, rules_, plan, specs, dims).train


def mk_sync(lm_, rules_, hwa, **kw):
    return build_hwa_bundles(lm_, rules_, SyncPlan(hwa=hwa, **kw)).sync


# ---- leg A: mesh-native shard_map path ------------------------------------
mesh_train = mk_train(lm, rules, hwa_cfg)
mesh_train_c = mesh_train.lower(mesh).compile()
a_inner, a_opt = stack2(params), jax.vmap(opt.init)(stack2(params))
with mesh:
    for step in range(N_STEPS):
        a_inner, a_opt, a_losses = mesh_train_c(a_inner, a_opt,
                                                batches(step))
check("mesh-native: finite per-replica losses",
      bool(jnp.all(jnp.isfinite(a_losses))))

# ---- leg B: vmap path compiled on the same mesh ---------------------------
vmap_train = mk_train(lm, rules, hwa_cfg, mesh_native=False)
vmap_train_c = vmap_train.lower(mesh).compile()
b_inner, b_opt = stack2(params), jax.vmap(opt.init)(stack2(params))
with mesh:
    for step in range(N_STEPS):
        b_inner, b_opt, _ = vmap_train_c(b_inner, b_opt, batches(step))

# ---- leg C: single-device vmap oracle -------------------------------------
def one(p, o, b):
    (l, m), g = jax.value_and_grad(
        lambda q: lm.loss(q, b), has_aux=True)(p)
    upd, o2 = opt.update(g, o, p, LR)
    return apply_updates(p, upd), o2, l


c_inner, c_opt = stack2(params), jax.vmap(opt.init)(stack2(params))
for step in range(N_STEPS):
    c_inner, c_opt, _ = jax.vmap(one)(c_inner, c_opt, batches(step))

err_ab = tree_err(a_inner, b_inner)
err_ac = tree_err(a_inner, c_inner)
check(f"mesh-native == vmap path after {N_STEPS} steps "
      f"(err={err_ab:.2e})", err_ab < 1e-5)
check(f"mesh-native == single-device oracle (err={err_ac:.2e})",
      err_ac < 1e-5)

# ---- sync: mesh-native vs stacked oracle ----------------------------------
# oracles first (the sync bundle donates its inputs), on HOST copies
a_host = to_host(a_inner)
a_host2 = a_host                      # same diverged state for the kernel leg
a_inner2 = jax.tree.map(jnp.array, a_host)   # fresh copies: sync donates
outer_oracle = jax.tree.map(lambda x: jnp.mean(x, 0), a_host)
ws_oracle, wa_oracle = window_update(
    window_init(params, hwa_cfg.window), outer_oracle)

sync = mk_sync(lm, rules, hwa_cfg)
sync_c = sync.lower(mesh).compile()
spec = sync.pack_spec               # window state is packed (I, P)/(P,)
check(f"sync: pack_spec is shard-aware (axes={spec.axes}, "
      f"shards={spec.shards})", spec.shards > 1 and len(spec.axes) >= 1)
ring = jnp.zeros((hwa_cfg.window, spec.padded), jnp.float32)
total = jnp.zeros((spec.padded,), jnp.float32)
zero = jnp.zeros((), jnp.int32)
with mesh:
    (s_inner, s_ring, s_total, s_count, s_nidx, s_wa,
     s_cycle) = sync_c(a_inner, ring, total, zero, zero, zero)
check("sync: replicas equal after restart",
      tree_err(jax.tree.map(lambda x: x[0], s_inner),
               jax.tree.map(lambda x: x[1], s_inner)) == 0.0)
err_outer = tree_err(jax.tree.map(lambda x: x[0], s_inner), outer_oracle)
check(f"sync: restart == stacked mean (err={err_outer:.2e})",
      err_outer < 1e-5)
err_wa = tree_err(s_wa, wa_oracle)
check(f"sync: window average == oracle (err={err_wa:.2e})", err_wa < 1e-5)
check("sync: count/cycle advanced",
      int(s_count) == 1 and int(s_cycle) == 1)

# ---- mesh-RESIDENT kernel sync: the Pallas path runs on the mesh ----------
# Bit-parity vs (a) the single-device fused kernel and (b) the per-leaf
# reference — the packed layouts differ (shard-aware vs contiguous), so
# all comparisons go through unpacked leaf views of host copies.
hwa_cfg_k = HWAConfig(n_replicas=K, window=3, use_kernels=True)
sync_k = mk_sync(lm, rules, hwa_cfg_k)
sync_kc = sync_k.lower(mesh).compile()
spec_k = sync_k.pack_spec
ring_k = jnp.zeros((hwa_cfg_k.window, spec_k.padded), jnp.float32)
total_k = jnp.zeros((spec_k.padded,), jnp.float32)
with mesh:
    out_k = sync_kc(a_inner2, ring_k, total_k, zero, zero, zero)
(k_inner, k_ring, k_total, k_count, k_nidx, k_wa, k_cycle) = out_k
k_ring_h, k_total_h = to_host(k_ring), to_host(k_total)

# (a) single-device fused path (one hwa_sync_packed launch, default spec)
from repro.kernels import ops as kops
spec1 = pack_spec(params)
stacked1 = pack_stacked(a_host2, spec1)
ring1, total1, avg1 = kops.hwa_sync_packed(
    stacked1, jnp.zeros((hwa_cfg_k.window, spec1.padded), jnp.float32),
    jnp.zeros((spec1.padded,), jnp.float32), zero, jnp.zeros(()),
    jnp.ones(()))
check("mesh-resident kernel sync: W̿ bit-equal to single-device fused",
      tree_equal(k_wa, unpack(avg1, spec1)))
check("mesh-resident kernel sync: restart bit-equal to fused ring slot",
      tree_equal(jax.tree.map(lambda x: x[0], k_inner),
                 unpack(ring1[0], spec1)))
check("mesh-resident kernel sync: ring slot bit-equal",
      tree_equal(unpack(k_ring_h[0], spec_k), unpack(ring1[0], spec1)))
check("mesh-resident kernel sync: total bit-equal",
      tree_equal(unpack(k_total_h, spec_k), unpack(total1, spec1)))

# (b) per-leaf reference (kernel-matching math: mean = sum × 1/K)
from repro.kernels import ref as kref
ring_tree = jax.tree.map(
    lambda x: jnp.zeros((hwa_cfg_k.window,) + x.shape), params)
total_tree = jax.tree.map(jnp.zeros_like, params)
triples = jax.tree.map(
    lambda s, r, t: kref.wa_sync_fused_ref(s, r, t, 0, 0.0, 1.0),
    a_host2, ring_tree, total_tree)
is3 = lambda x: isinstance(x, tuple) and len(x) == 3
leaf_wa = jax.tree.map(lambda t: t[2], triples, is_leaf=is3)
check("mesh-resident kernel sync: W̿ bit-equal to per-leaf reference",
      tree_equal(k_wa, leaf_wa))
check("mesh-resident kernel sync: window advanced",
      int(k_count) == 1 and int(k_cycle) == 1)

# exactly ONE Pallas launch per sync, counted structurally in the jaxpr
jaxpr_k = jax.make_jaxpr(sync_k.fn)(*sync_k.abstract_args)
check(f"mesh-resident kernel sync: one pallas_call in the jaxpr "
      f"(found {count_pallas_calls(jaxpr_k)})",
      count_pallas_calls(jaxpr_k) == 1)

# ---- HLO structure: replica-axis traffic only in hwa_sync -----------------
train_hlo = mesh_train_c.as_text()
cross_train = collectives_crossing_axis(train_hlo, mesh, "replica")
check(f"train step: zero replica-crossing collectives "
      f"(found {len(cross_train)})", len(cross_train) == 0)

for label, compiled in [("sync", sync_c), ("kernel sync", sync_kc)]:
    audit = sync_collective_audit(compiled.as_text(), mesh)
    check(f"{label} step: exactly one replica-crossing collective, the "
          f"weight all-reduce (found {[op for op, _ in audit['replica']]})",
          audit["replica_allreduce_only"])
    n_other = {ax: len(h) for ax, h in audit["other"].items()}
    check(f"{label} step: packed-W̄ assembly is collective-free "
          f"(non-replica crossings: {n_other})", audit["assembly_free"])

# the legacy (non-mesh-resident) fallback is a HARD ERROR on multi-device
# CPU meshes (see launch/sync/legacy.py); REPRO_ALLOW_LEGACY_ASSEMBLY=1
# is the escape hatch for HLO-introspection-only callers, under which it
# still compiles and structurally pays the assembly redistribution the
# aligned layout removes
_prior_hatch = os.environ.pop("REPRO_ALLOW_LEGACY_ASSEMBLY", None)
try:
    legacy_raised = False
    try:
        mk_sync(lm, rules, hwa_cfg, mesh_resident=False)
    except RuntimeError:
        legacy_raised = True
    check("legacy fallback: hard error on the multi-device CPU mesh",
          legacy_raised)
    os.environ["REPRO_ALLOW_LEGACY_ASSEMBLY"] = "1"
    sync_legacy = mk_sync(lm, rules, hwa_cfg, mesh_resident=False)
    legacy_audit = sync_collective_audit(
        sync_legacy.lower(mesh).compile().as_text(), mesh)
    n_legacy = sum(len(h) for h in legacy_audit["other"].values())
    check(f"legacy fallback (escape hatch): compiles, assembly pays "
          f"non-replica collectives (found {n_legacy})", n_legacy >= 1)
finally:
    if _prior_hatch is None:
        os.environ.pop("REPRO_ALLOW_LEGACY_ASSEMBLY", None)
    else:
        os.environ["REPRO_ALLOW_LEGACY_ASSEMBLY"] = _prior_hatch

# ---- FSDP mixed tilings: the GROUPED mesh-resident packed sync ------------
# fsdp=True rules shard "embed" dims over data too, so leaves tile over
# data-only, model-only, AND data×model jointly — no single packed
# super-axis covers them, and before the grouped layout this tree fell
# back to the legacy GSPMD assembly (the hard error asserted above). Now
# choose_resident_spec returns a grouped PackSpec (one PackGroup per
# placement key), the window state rides as per-group buffer tuples, the
# sync costs one kernel launch per group and STILL exactly one replica
# all-reduce with zero assembly collectives, and every result is
# bit-identical to the per-leaf reference (same math, different layout).
from repro.common.packing import merge_groups, window_buffers

rules_f = make_tp_rules(mesh, replica_axis="replica", fsdp=True)
sync_f = mk_sync(lm, rules_f, hwa_cfg_k)                   # builds: no
check("fsdp sync: grouped layout chosen, no legacy-assembly error "     # raise
      f"(n_groups={sync_f.pack_spec.n_groups})",
      sync_f.pack_spec.is_grouped and sync_f.pack_spec.n_groups >= 2)
spec_f = sync_f.pack_spec
sync_fc = sync_f.lower(mesh).compile()
ring_f, total_f = window_buffers(spec_f, hwa_cfg_k.window)
with mesh:
    (fs_inner, fs_ring, fs_total, fs_count, fs_nidx, fs_wa,
     fs_cycle) = sync_fc(jax.tree.map(jnp.array, a_host2), ring_f, total_f,
                         zero, zero, zero)
check("fsdp sync: W̿ bit-equal to per-leaf reference",
      tree_equal(fs_wa, leaf_wa))
check("fsdp sync: restart bit-equal to single-device fused ring slot",
      tree_equal(jax.tree.map(lambda x: x[0], fs_inner),
                 unpack(ring1[0], spec1)))
fs_ring_h = to_host(fs_ring)
check("fsdp sync: merged group ring slot bit-equal",
      tree_equal(unpack(merge_groups(tuple(r[0] for r in fs_ring_h),
                                     spec_f), spec_f),
                 unpack(ring1[0], spec1)))
check("fsdp sync: window advanced",
      int(fs_count) == 1 and int(fs_cycle) == 1)
audit_f = sync_collective_audit(sync_fc.as_text(), mesh,
                                n_groups=spec_f.n_groups)
check("fsdp sync: grouped audit ok (one replica all-reduce, zero "
      f"assembly crossings; replica="
      f"{[op for op, _ in audit_f['replica']]})",
      audit_f["grouped_sync_ok"])
n_launch_f = count_pallas_calls(
    jax.make_jaxpr(sync_f.fn)(*sync_f.abstract_args))
check(f"fsdp sync: pallas launches ≤ n_groups "
      f"({n_launch_f} ≤ {spec_f.n_groups})",
      1 <= n_launch_f <= spec_f.n_groups)

# ---- resilient (alive-masked) sync ----------------------------------------
# With hwa_cfg.resilient the K-mean becomes the alive-masked elastic
# mean (repro.resilience.health). Contract checked here: (a) with every
# replica healthy it is BITWISE identical to the plain packed sync —
# masking with an all-true mask adds exact zeros and the renormalized
# inverse pins the trace-time f32(1/K); (b) a NaN-poisoned replica is
# excluded and re-seeded from the finite W̄ of the survivors; (c) the
# lowered HLO carries exactly 2 replica-crossing all-reduces (k_alive +
# masked weights, unmergeable by construction) plus the budgeted
# non-replica health-stats psum — audited via the bundle's own contract.
from repro.analysis.collectives import check_collective_contract
from repro.resilience.faults import poison_replica

hwa_cfg_r = HWAConfig(n_replicas=K, window=3, resilient=True)
sync_r = mk_sync(lm, rules, hwa_cfg_r)
sync_rc = sync_r.lower(mesh).compile()
spec_r = sync_r.pack_spec
check("resilient sync: same packed layout as the plain sync",
      spec_r.padded == spec.padded)


def fresh_window_r():
    return (jnp.zeros((hwa_cfg_r.window, spec_r.padded), jnp.float32),
            jnp.zeros((spec_r.padded,), jnp.float32))


ring_r, total_r = fresh_window_r()
with mesh:
    (r_inner, r_ring, r_total, r_count, r_nidx, r_wa, r_cycle,
     r_alive) = sync_rc(jax.tree.map(jnp.array, a_host), ring_r, total_r,
                        zero, zero, zero)
check("resilient sync (all healthy): alive mask is all-true",
      bool(jnp.all(r_alive)) and r_alive.shape == (K,))
check("resilient sync (all healthy): restart BIT-equal to plain sync",
      tree_equal(to_host(r_inner), to_host(s_inner)))
check("resilient sync (all healthy): W̿ BIT-equal to plain sync",
      tree_equal(to_host(r_wa), to_host(s_wa)))
check("resilient sync (all healthy): ring/total BIT-equal to plain sync",
      tree_equal(to_host((r_ring, r_total)), to_host((s_ring, s_total))))
check("resilient sync (all healthy): counters match plain sync",
      int(r_count) == int(s_count) and int(r_cycle) == int(s_cycle))

# (b) poison replica 1: survivors' mean is replica 0 exactly (K=2), so
# every replica restarts bit-equal to replica 0's pre-sync weights
poisoned = jax.tree.map(jnp.array, poison_replica(a_host, 1))
ring_r, total_r = fresh_window_r()
with mesh:
    (p_inner, _, _, _, _, p_wa, _, p_alive) = sync_rc(
        poisoned, ring_r, total_r, zero, zero, zero)
check("resilient sync (poisoned): alive mask excludes replica 1",
      bool(p_alive[0]) and not bool(p_alive[1]))
check("resilient sync (poisoned): W̿ finite",
      all(bool(jnp.all(jnp.isfinite(x))) for x in jax.tree.leaves(p_wa)))
rep0 = jax.tree.map(lambda x: x[0], a_host)
check("resilient sync (poisoned): restart bit-equal to the lone "
      "survivor's weights",
      tree_equal(to_host(jax.tree.map(lambda x: x[0], p_inner)), rep0)
      and tree_equal(to_host(jax.tree.map(lambda x: x[1], p_inner)), rep0))

# (c) collective structure: the bundle's declarative contract (2 replica
# all-reduces + 1 budgeted non-replica health psum, zero assembly)
r_contract = check_collective_contract(sync_rc.as_text(), mesh,
                                       sync_r.contract.collectives)
check(f"resilient sync: collective contract holds "
      f"(violations={r_contract['violations']})", r_contract["ok"])
n_rep_r = len(collectives_crossing_axis(sync_rc.as_text(), mesh,
                                        "replica"))
check(f"resilient sync: exactly 2 replica-crossing collectives "
      f"(found {n_rep_r})", n_rep_r == 2)

# vmap-path train step, for contrast, is *allowed* replica traffic (GSPMD
# may or may not insert it) — we only report it, the guarantee is the
# shard_map path's.
cross_vmap = collectives_crossing_axis(vmap_train_c.as_text(), mesh,
                                       "replica")
print(f"INFO vmap-path train step replica-crossing collectives: "
      f"{len(cross_vmap)}")

# ---- two-level sync tree: flat ↔ tree ↔ per-leaf bit-parity ---------------
# K = 4 replicas as 2 pods × 2 members on the pod-carved (2,2,2) mesh.
# The tree's outer sync computes the mean as the grouped psum composition
# (per-pod psum of 1/K-pre-scaled partials, then the cross-pod psum over
# CONTIGUOUS pods); with power-of-two counts every collective is a
# 2-member all-reduce (one commutative IEEE add) and every local sum uses
# the canonical halving order, so the composition is bit-identical —
# 0 ULP — to (a) the FLAT path (the vmap-path flat plan with two
# replicas resident per device on the plain mesh: local sum + one
# 2-member psum)
# and (b) the per-leaf host reference online_average_grouped
# (docs/ARCHITECTURE.md §4).
from repro.core.online import online_average_grouped, pod_mean_grouped
from repro.launch.mesh import make_tree_test_mesh
from repro.launch.steps import TwoLevel

K4 = 4
mesh_t = make_tree_test_mesh()          # (pod=2, replica=2, model=2)
rules_t = make_tp_rules(mesh_t, replica_axis=("pod", "replica"))
hwa4 = HWAConfig(n_replicas=K4, window=3, use_kernels=True, outer_every=2)
topo = TwoLevel("replica", "pod", outer_every=2)

# tuple-axis train step: collective-free over BOTH replica-population
# axes (the TwoLevel plan resolves replica_axis to ("pod", "replica"))
tree_bundles = build_hwa_bundles(lm, rules_t,
                                 SyncPlan(hwa=hwa4, topology=topo,
                                          optimizer="sgd", lr=LR),
                                 specs, dims)
tree_train = tree_bundles.train
tree_train_c = tree_train.lower(mesh_t).compile()


def batches4(step):
    ks = jax.random.split(jax.random.key(300 + step), 2)
    return {"tokens": jax.random.randint(ks[0], (K4, B, S), 0,
                                         cfg.vocab_size),
            "targets": jax.random.randint(ks[1], (K4, B, S), 0,
                                          cfg.vocab_size)}


stack4 = lambda t: jax.tree.map(lambda x: jnp.stack([x] * K4), t)
t_inner0, t_opt0 = stack4(params), jax.vmap(opt.init)(stack4(params))
with mesh_t:
    t_inner0, t_opt0, t_losses = tree_train_c(t_inner0, t_opt0, batches4(0))
check("tree train step: finite per-replica losses",
      bool(jnp.all(jnp.isfinite(t_losses))))
tree_train_hlo = tree_train_c.as_text()
for ax in ("pod", "replica"):
    hits = collectives_crossing_axis(tree_train_hlo, mesh_t, ax)
    check(f"tree train step: zero {ax}-crossing collectives "
          f"(found {len(hits)})", len(hits) == 0)

# diverged 4-replica state (host-materialized; oracles below need it)
div4 = jax.tree.map(
    lambda x: x[None] + 0.1 * jax.random.normal(jax.random.key(11),
                                                (K4,) + x.shape), params)
div4_host = to_host(div4)
zero = jnp.zeros((), jnp.int32)


def run_sync(bundle, run_mesh, state, with_cycle):
    spec_ = bundle.pack_spec
    ring_ = jnp.zeros((hwa4.window, spec_.padded), jnp.float32)
    total_ = jnp.zeros((spec_.padded,), jnp.float32)
    c = bundle.lower(run_mesh).compile()
    extra = (zero,) if with_cycle else ()
    with run_mesh:
        return c(state, ring_, total_, zero, zero, *extra), c


# leg T: two-level OUTER sync (inner psum + cross-pod psum + window push)
outer_b = tree_bundles.sync
(t_out, outer_c) = run_sync(outer_b, mesh_t,
                            jax.tree.map(jnp.array, div4_host), True)
t_inner, _, _, t_count, _, t_wa, t_cycle = t_out
# leg F: FLAT path, K=4 with two replicas resident per device on the
# plain (replica=2, data=2, model=2) mesh (flat cfg: the flat builder
# refuses a silently-ignored outer_every; the sync math is identical)
import dataclasses
flat_b = mk_sync(lm, rules, dataclasses.replace(hwa4, outer_every=1),
                 mesh_native=False)
(f_out, _) = run_sync(flat_b, mesh,
                      jax.tree.map(jnp.array, div4_host), False)
f_inner, _, _, _, _, f_wa = f_out
# leg R: per-leaf host reference (canonical grouped mean; the first
# window push leaves W̿ == W̄ exactly, so it doubles as the W̿ oracle)
r_mean = online_average_grouped(div4_host, topo.pods(mesh_t))

check("two-level: all replicas restart equal",
      all(tree_equal(jax.tree.map(lambda x: x[0], t_inner),
                     jax.tree.map(lambda x, i=i: x[i], t_inner))
          for i in range(1, K4)))
check("two-level restart bit-equal to FLAT restart",
      tree_equal(jax.tree.map(lambda x: x[0], t_inner),
                 jax.tree.map(lambda x: x[0], f_inner)))
check("two-level W̿ bit-equal to FLAT W̿", tree_equal(t_wa, f_wa))
check("two-level restart bit-equal to per-leaf grouped reference",
      tree_equal(jax.tree.map(lambda x: x[0], t_inner), r_mean))
check("two-level W̿ bit-equal to per-leaf grouped reference",
      tree_equal(t_wa, r_mean))
check("two-level: window advanced on the outer sync",
      int(t_count) == 1 and int(t_cycle) == 1)

# the extended audit, per level: the outer sync is one inner-only + one
# outer-only all-reduce (no mixed groups, assembly-free) ...
audit_outer = sync_collective_audit(outer_c.as_text(), mesh_t,
                                    replica_axis="replica",
                                    outer_axis="pod")
check("two-level outer sync: audit outer_sync_ok "
      f"(inner={len(audit_outer['replica'])}, "
      f"outer={len(audit_outer['outer'])}, "
      f"mixed={len(audit_outer['mixed'])})", audit_outer["outer_sync_ok"])

# ... and the INNER sync crosses ONLY the inner (per-pod) groups
inner_b = tree_bundles.inner_sync
inner_c = inner_b.lower(mesh_t).compile()
# a profiler trace tells the programs apart by these names
names = [c.as_text().split(",", 1)[0].split()[-1]
         for c in (mesh_train_c, sync_c, inner_c)]
check(f"mesh programs are named apart: {names}",
      names == ["jit_mesh_train_step", "jit_mesh_sync_step",
                "jit_mesh_inner_sync_step"])
with mesh_t:
    i_inner = inner_c(jax.tree.map(jnp.array, div4_host))
audit_inner = sync_collective_audit(inner_c.as_text(), mesh_t,
                                    replica_axis="replica",
                                    outer_axis="pod")
check("two-level inner sync: audit inner_sync_ok (zero cross-pod "
      f"collectives, found {len(audit_inner['outer'])})",
      audit_inner["inner_sync_ok"])
pm = pod_mean_grouped(div4_host, topo.pods(mesh_t))
pm_expanded = jax.tree.map(
    lambda m: jnp.concatenate([m[0:1], m[0:1], m[1:2], m[1:2]]), pm)
check("inner sync: restart bit-equal to per-pod means",
      tree_equal(i_inner, pm_expanded))
check("inner sync: pods stay diverged (no cross-pod averaging)",
      not tree_equal(jax.tree.map(lambda x: x[0], i_inner),
                     jax.tree.map(lambda x: x[2], i_inner)))

# k_local > 2 regression: with 4 replicas RESIDENT per device the kernel
# partial mean must yield to the canonical halving sum (the kernel's row
# reduction order is an XLA detail beyond 2 rows — packed.py gates it),
# keeping the flat kernel path bit-equal to the canonical/grouped means
from repro.core.online import online_average_canonical

K8 = 8
div8_host = to_host(jax.tree.map(
    lambda x: x[None] + 0.1 * jax.random.normal(jax.random.key(13),
                                                (K8,) + x.shape), params))
hwa8 = HWAConfig(n_replicas=K8, window=3, use_kernels=True)
flat8 = mk_sync(lm, rules, hwa8, mesh_native=False)  # k_local=4
spec8 = flat8.pack_spec
flat8_c = flat8.lower(mesh).compile()
with mesh:
    out8 = flat8_c(jax.tree.map(jnp.array, div8_host),
                   jnp.zeros((hwa8.window, spec8.padded), jnp.float32),
                   jnp.zeros((spec8.padded,), jnp.float32), zero, zero)
check("flat kernel sync, k_local=4: restart bit-equal to canonical "
      "halving mean",
      tree_equal(jax.tree.map(lambda x: x[0], out8[0]),
                 online_average_canonical(div8_host)))

# ---- flash-pallas train step: fully-manual kernel attention ---------------
# cfg.attn_impl == "flash_pallas" switches the mesh-native train step to a
# FULLY-manual shard_map (Pallas kernels are opaque to GSPMD — under the
# partial-auto map XLA would run them per-shard with global-shape
# semantics): attention fwd + the two recompute-bwd sweeps execute on
# true local shapes, data parallelism is an explicit grad pmean, and the
# bundle declares an EXACT LaunchBudget. Checks: finite losses, parity
# with a single-device flash_pallas oracle, zero replica-crossing
# collectives, and the launch counts — structural (jaxpr == contract)
# AND per-layer physical (scan-trip-weighted: 1 fwd + 2 bwd per layer).
cfg_fp = cfg.with_(attn_impl="flash_pallas")
lm_fp = build_model(cfg_fp)
flash_train = mk_train(lm_fp, rules, hwa_cfg)
flash_train_c = flash_train.lower(mesh).compile()
fp_inner, fp_opt = stack2(params), jax.vmap(opt.init)(stack2(params))
with mesh:
    for step in range(N_STEPS):
        fp_inner, fp_opt, fp_losses = flash_train_c(fp_inner, fp_opt,
                                                    batches(step))
check("flash-pallas train: finite per-replica losses",
      bool(jnp.all(jnp.isfinite(fp_losses))))


def one_fp(p, o, b):
    (l, m), g = jax.value_and_grad(
        lambda q: lm_fp.loss(q, b), has_aux=True)(p)
    upd, o2 = opt.update(g, o, p, LR)
    return apply_updates(p, upd), o2, l


cfp_inner, cfp_opt = stack2(params), jax.vmap(opt.init)(stack2(params))
for step in range(N_STEPS):
    cfp_inner, cfp_opt, _ = jax.vmap(one_fp)(cfp_inner, cfp_opt,
                                             batches(step))
err_fp = tree_err(fp_inner, cfp_inner)
check(f"flash-pallas train == single-device oracle after {N_STEPS} steps "
      f"(err={err_fp:.2e})", err_fp < 1e-5)

flash_hlo = flash_train_c.as_text()
cross_fp = collectives_crossing_axis(flash_hlo, mesh, "replica")
check(f"flash-pallas train: zero replica-crossing collectives "
      f"(found {len(cross_fp)})", len(cross_fp) == 0)

fp_jaxpr = jax.make_jaxpr(flash_train.fn)(*flash_train.abstract_args)
n_struct = count_pallas_calls(fp_jaxpr)
fp_budget = flash_train.contract.launch
check(f"flash-pallas train: structural jaxpr launches == LaunchBudget "
      f"({n_struct} == [{fp_budget.min}, {fp_budget.max}])",
      fp_budget is not None and fp_budget.min == n_struct == fp_budget.max)


def physical_launches(jaxpr):
    """Scan-trip-weighted launch count: the layer scan is one jaxpr eqn,
    but each trip is a real launch at run time."""
    while hasattr(jaxpr, "jaxpr"):
        jaxpr = jaxpr.jaxpr
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            n += 1
            continue
        mult = (eqn.params.get("length", 1)
                if eqn.primitive.name == "scan" else 1)
        for param in eqn.params.values():
            for sub in (param if isinstance(param, (list, tuple))
                        else (param,)):
                if hasattr(sub, "eqns") or hasattr(sub, "jaxpr"):
                    n += mult * physical_launches(sub)
    return n


n_phys = physical_launches(fp_jaxpr)
check(f"flash-pallas train: 1 fwd + 2 bwd launches per layer "
      f"({n_phys} == 3 × {cfg.n_layers})", n_phys == 3 * cfg.n_layers)

# ---- compressed WA precision: bounded-ULP parity (PR 10) ------------------
# The compressed legs reuse the exact-f32 results above as oracles and
# bound the deviation in RELATIVE ULPs of the compressed dtype at the
# buffer's working scale (repro.common.quant.rel_ulp_error). Budgets come
# from benchmarks/thresholds.json `ulp_budgets` — the SAME numbers
# bench-check guards, so the harness and the bench trajectory cannot
# drift apart. (The f32 default's 0-ULP guarantee is every bit-equality
# check above.)
import json

from repro.common.quant import rel_ulp_error
from repro.launch.steps import window_state_args

with open(os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))),
        "benchmarks", "thresholds.json")) as f:
    ULP_BUDGETS = json.load(f)["ulp_budgets"]


def max_rel_ulp(ref, got, tok):
    return max(rel_ulp_error(r, g, tok)
               for r, g in zip(jax.tree.leaves(ref), jax.tree.leaves(got)))


# leg C-bf16: flat kernel sync with a bf16 ring (Kahan-compensated f32
# total) on the SAME diverged state as the f32 kernel leg. The restart
# is the DECODED stored mean (packed.py: ring slot and live replicas
# agree bitwise), so it must be exactly the bf16-rounding of the f32
# leg's restart; W̿ reads back through the compressed ring and gets the
# bf16 budget.
from repro.common.quant import decode_slot, encode_slot

sync_bf = mk_sync(lm, rules, hwa_cfg_k, wa_dtype="bf16")
win_bf = window_state_args(sync_bf)
nb = len(win_bf) - 3                      # ring, [scales], ..., [comp]
with mesh:
    out_bf = sync_bf.lower(mesh).compile()(
        jax.tree.map(jnp.array, a_host), *win_bf)
bf_inner, bf_wa = out_bf[0], out_bf[3 + nb]
check("bf16-ring kernel sync: restart == bf16-rounded f32 restart "
      "(ring slot and replicas agree bitwise)",
      tree_equal(bf_inner, jax.tree.map(
          lambda x: decode_slot(encode_slot(x, "bf16")[0]), k_inner)))
err_bf = max_rel_ulp(k_wa, bf_wa, "bf16")
check(f"bf16-ring kernel sync: W̿ within {ULP_BUDGETS['bf16']} rel ULPs "
      f"of exact f32 (err={err_bf:.2f})", err_bf <= ULP_BUDGETS["bf16"])

# leg C-fp8: the full compressed tree — fp8 ring (per-block scales) AND
# fp8 cross-pod comms — against the exact f32 tree leg T.
sync_f8 = build_hwa_bundles(
    lm, rules_t, SyncPlan(hwa=hwa4, topology=topo,
                          wa_dtype="fp8", comms_dtype="fp8")).sync
win_f8 = window_state_args(sync_f8)
nf = len(win_f8) - 3
f8_c = sync_f8.lower(mesh_t).compile()
with mesh_t:
    out_f8 = f8_c(jax.tree.map(jnp.array, div4_host), *win_f8)
err_f8 = max_rel_ulp(t_wa, out_f8[3 + nf], "fp8")
check(f"fp8 tree sync (fp8 ring + fp8 comms): W̿ within "
      f"{ULP_BUDGETS['fp8']} rel ULPs of exact f32 (err={err_f8:.2f})",
      err_f8 <= ULP_BUDGETS["fp8"])
audit_f8 = sync_collective_audit(f8_c.as_text(), mesh_t,
                                 replica_axis="replica", outer_axis="pod")
check(f"fp8 tree sync: outer hop is the gather pair "
      f"(found {len(audit_f8['outer'])})", len(audit_f8["outer"]) == 2)
check("fp8 tree sync: compressed payload crosses the wire as u8",
      any("u8[" in line for _, line in audit_f8["outer"]))

print("ALL_OK" if ok else "SOME_FAILED")
raise SystemExit(0 if ok else 1)
