"""Benchmark orchestrator — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (and tees per-table JSON into
experiments/bench/). The kernel suite's structured result (per-benchmark
µs + derived bytes/launches/padding) is additionally written to
``BENCH_kernels.json`` at the repo root so the perf trajectory is tracked
across PRs, not just printed.

  PYTHONPATH=src python -m benchmarks.run [--only table2,roofline]
"""
import argparse
import json
import os
import sys
import time

from repro.common.compile_cache import use_compile_cache

BENCHES = [
    ("table2", "benchmarks.table2_methods"),
    ("table3", "benchmarks.table3_ablation"),
    ("table4", "benchmarks.table4_k_models"),
    ("fig13", "benchmarks.fig13_window"),
    ("fig2", "benchmarks.fig2_lr_sensitivity"),
    ("fig7", "benchmarks.fig7_convergence"),
    ("fig9", "benchmarks.fig9_interpolation"),
    ("comm", "benchmarks.comm_amortization"),
    ("mesh_comm", "benchmarks.mesh_comm"),
    ("kernels", "benchmarks.kernel_bench"),
    ("sync_tree", "benchmarks.sync_tree"),
    ("comms", "benchmarks.comms_bench"),
    ("serve", "benchmarks.serve_bench"),
    ("roofline", "benchmarks.roofline"),
]

# Benchmarks whose structured result is persisted into BENCH_kernels.json
# at the repo root (cross-PR perf trajectory). "kernels" merges its
# record at the top level (historical layout); "sync_tree" and "serve"
# append under their own keys — existing keys from other benchmarks
# survive.
_BENCH_JSON_KEY = {"kernels": None, "sync_tree": "sync/tree",
                   "comms": "sync/comms", "serve": "serve"}


def _merge_bench_json(name: str, result: dict) -> None:
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(root, "BENCH_kernels.json")
    data = {}
    if os.path.exists(path):
        try:
            with open(path) as f:
                data = json.load(f)
        except (OSError, json.JSONDecodeError):
            data = {}
    key = _BENCH_JSON_KEY[name]
    if key is None:
        # kernels owns the top level: drop its stale keys (a renamed or
        # removed benchmark must not linger as a "current" measurement),
        # keeping only the blocks other benchmarks own
        keep = {k for k in _BENCH_JSON_KEY.values() if k is not None}
        data = {k: v for k, v in data.items() if k in keep}
        data.update(result)
    else:
        data[key] = result
    with open(path, "w") as f:
        json.dump(data, f, indent=2, sort_keys=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None
    os.makedirs("experiments/bench", exist_ok=True)
    use_compile_cache()
    print("name,us_per_call,derived")
    rows = []
    failed = []

    def sink(line):
        print(line, flush=True)
        rows.append(line)

    for name, module in BENCHES:
        if only and name not in only:
            continue
        t0 = time.time()
        mod = __import__(module, fromlist=["main"])
        try:
            result = mod.main(print_fn=sink)
        except Exception as e:  # noqa: BLE001 — report, go on, fail at end
            result = None
            failed.append(name)
            sink(f"{name}/ERROR,0,{type(e).__name__}: {e}")
        sink(f"{name}/wall_s,{(time.time()-t0)*1e6:.0f},done")
        if name in _BENCH_JSON_KEY and isinstance(result, dict) and result:
            _merge_bench_json(name, result)
    with open("experiments/bench/rows.csv", "w") as f:
        f.write("name,us_per_call,derived\n")
        f.write("\n".join(rows) + "\n")
    if failed:
        sys.exit(f"benchmarks failed: {', '.join(failed)}")


if __name__ == '__main__':
    main()
