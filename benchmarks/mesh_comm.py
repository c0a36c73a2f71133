"""Mesh-native HWA communication amortization, measured from real lowered
HLO (not dry-run artifacts): per-sync replica-axis bytes vs a per-step
gradient all-reduce baseline, on a (2,2,2) forced-host-device mesh.

The numbers quantify the paper's §I claim with the shard_map path's
structural guarantee: the inner train step's replica-axis traffic is
*identically zero* (checked), so inter-replica bytes/step = sync_bytes/H.

Runs the device-hungry part in a subprocess so the forced 8-device host
platform never leaks into the benchmark process.
"""
import json
import sys

from benchmarks.common import csv_row

_WORKER_FLAG = "--mesh-comm-worker"


def _worker():
    import jax
    import jax.numpy as jnp

    from repro.configs import get_smoke_config
    from repro.core.hwa import HWAConfig
    from repro.launch.hlo import collectives_crossing_axis, result_bytes
    from repro.launch.mesh import make_test_mesh
    from repro.launch.specs import input_specs
    from repro.launch.steps import (make_hwa_train_step,
                                    make_mesh_hwa_sync_step,
                                    make_mesh_hwa_train_step)
    from repro.models.registry import build_model
    from repro.models.types import InputShape
    from repro.sharding.rules import make_tp_rules

    mesh = make_test_mesh((2, 2, 2), ("replica", "data", "model"))
    rules = make_tp_rules(mesh, replica_axis="replica")
    cfg = get_smoke_config("granite-3-2b")
    lm = build_model(cfg)
    hwa_cfg = HWAConfig(n_replicas=2, window=3)
    shape = InputShape("bench", seq_len=32, global_batch=8, kind="train")
    specs, dims = input_specs(cfg, shape)

    def crossing_bytes(compiled):
        hits = collectives_crossing_axis(compiled.as_text(), mesh, "replica")
        return len(hits), result_bytes(hits)

    out = {}
    mesh_train = make_mesh_hwa_train_step(
        lm, rules, specs, dims, hwa_cfg, optimizer="sgd").lower(mesh).compile()
    out["mesh_train"] = crossing_bytes(mesh_train)
    vmap_train = make_hwa_train_step(
        lm, rules, specs, dims, hwa_cfg, optimizer="sgd").lower(mesh).compile()
    out["vmap_train"] = crossing_bytes(vmap_train)
    sync = make_mesh_hwa_sync_step(
        lm, rules, hwa_cfg).lower(mesh).compile()
    out["sync"] = crossing_bytes(sync)
    n_params = sum(
        int(jnp.prod(jnp.asarray(l.shape)))
        for l in jax.tree.leaves(lm.abstract()[0]))
    out["param_bytes"] = 4 * n_params
    # flat-vs-tree sync topologies on the pod-carved (2,2,2) mesh — the
    # same record `make bench-sync` persists into BENCH_kernels.json.
    # Recomputed here (~3 s: three small sync-bundle compiles) rather
    # than read from that file: the worker subprocesses cannot share a
    # live record, and a stale file would silently misreport. Isolated
    # so a tree-path regression cannot void the unrelated replica-byte
    # measurements above (it surfaces as a tree/ERROR row).
    try:
        from benchmarks.sync_tree import tree_sync_record
        out["tree"] = tree_sync_record()
    except Exception as e:  # noqa: BLE001 — report and keep the rest
        out["tree"] = {"error": f"{type(e).__name__}: {e}"}
    print(json.dumps(out))


def main(print_fn=print):
    from benchmarks.common import run_forced_device_worker
    rec = run_forced_device_worker(__file__, _WORKER_FLAG,
                                   error_row="mesh_comm/ERROR",
                                   print_fn=print_fn)
    mesh_n, mesh_b = rec["mesh_train"]
    vmap_n, vmap_b = rec["vmap_train"]
    sync_n, sync_b = rec["sync"]
    print_fn(csv_row("mesh_comm/train_replica_bytes/mesh_native", 0.0,
                     f"collectives={mesh_n};bytes={mesh_b}"))
    print_fn(csv_row("mesh_comm/train_replica_bytes/vmap_path", 0.0,
                     f"collectives={vmap_n};bytes={vmap_b}"))
    print_fn(csv_row("mesh_comm/sync_replica_bytes", 0.0,
                     f"collectives={sync_n};bytes={sync_b};"
                     f"param_bytes={rec['param_bytes']}"))
    # amortization: inter-replica bytes per *step* when syncing every H
    for H in (1, 64, 391, 1024):
        per_step = mesh_b + sync_b / H
        print_fn(csv_row(f"mesh_comm/bytes_per_step/H={H}", 0.0,
                         f"mesh_native={per_step:.3e};"
                         f"per_step_allreduce={sync_b:.3e}"))
    # flat vs two-level tree: modeled ICI bytes per cycle of H₂ syncs on
    # the pod-carved (2,2,2) mesh (cross-pod traffic is the tree's win)
    tree = rec.get("tree", {})
    if "error" in tree:
        print_fn(csv_row("mesh_comm/sync_tree_cycle/ERROR", 0.0,
                         tree["error"].replace(",", ";")[:160]))
    for h2, c in tree.get("per_cycle", {}).items():
        print_fn(csv_row(
            f"mesh_comm/sync_tree_cycle/{h2}", 0.0,
            f"flat_ici={c['flat_ici_bytes']:.3e};"
            f"tree_ici={c['tree_ici_bytes']:.3e};"
            f"flat_pod={c['flat_pod_bytes']:.3e};"
            f"tree_pod={c['tree_pod_bytes']:.3e}"))
    return rec


if __name__ == "__main__":
    if _WORKER_FLAG in sys.argv:
        _worker()
    else:
        main()
