"""Readings that set a training cell's limits, on the chip, in one
process (the benchmark's own runs never run this):

  python3 chipbench/control.py --workload <cell> --seeds 12 \
      --first-seed <n> [--controls 3] [--out <file.json>]

For every seed it drives the cell as a run does, with a window of one
cycle, and reads the program's numbers against the reference: over a
dozen seeds or more, the largest is a number's lower reading. On the
first ``--controls`` seeds it also reads

- the control: the reference in float8 (``matmul="fp8"``) put in the
  program's place, and the sync's arithmetic in bfloat16;
- the faults that can be planted in the reference put in the program's
  place: ``half_batch`` (the loss over half of the rows). A step that
  returns its state unchanged reads 1 on ``change_norm_gap`` by
  construction and needs no run.

The smallest control or fault reading of a number is its upper reading.
Prints one JSON line per seed and a summary; ``--out`` also writes them.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
from chipbench import bench, compare  # noqa: E402


def seed_readings(driver, entry: dict, config: dict, traffic: dict,
                  seed: int, devices, controls: bool) -> dict:
    """One seed: the program's numbers, and with ``controls`` the
    control's and the planted fault's."""
    t0 = time.perf_counter()
    r = bench.Run(cell=entry, config=config, traffic=traffic, seed=seed,
                  seconds=0.0, devices=devices, window=bench.Window(False))
    out = driver.run(r)
    out.free()
    out.verify()
    row = {"seed": seed, "program": {c.name: c.value for c in r.checks}}
    if controls:
        info = out.info
        dims, key = info["dims"], info["key"]
        ref = driver.reference_readings(r, dims, key)
        for name, kw in (("control", {"matmul": "fp8"}),
                         ("half_batch", {"fault": "half_batch"})):
            alt = driver.reference_readings(r, dims, key, **kw)
            row[name] = compare.training_gaps(alt, ref)
        row["control"].update(driver.sync_numbers(
            traffic["replicas"], traffic["window"],
            info["captured"]["sync"], control=True))
    row["seconds"] = time.perf_counter() - t0
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, required=True)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    bench_doc = bench.benchmark()
    entry = bench.cell_entry(bench_doc, args.workload)
    traffic = bench.traffic_doc(args.workload)
    config = bench.config_doc(bench_doc, entry["config"])
    sys.path.insert(0, os.path.join(bench.ROOT, "src"))
    bench.pin_compile_cache()
    import jax
    devices = bench.require_chips(jax, entry["chips"])
    from repro.common.compile_cache import use_compile_cache
    use_compile_cache()
    driver = bench.load_module("drivers", traffic["driver"])
    rows = []
    for i in range(args.seeds):
        rows.append(seed_readings(driver, entry, config, traffic,
                                  args.first_seed + i, devices,
                                  i < args.controls))
        print(json.dumps(rows[-1]), flush=True)

    names = list(rows[0]["program"])
    summary = {}
    for n in names:
        lower = max(r["program"][n] for r in rows)
        uppers = [r[k][n] for r in rows for k in ("control", "half_batch")
                  if k in r and n in r[k]]
        summary[n] = {"lower": lower,
                      "upper": min(uppers) if uppers else None}
    print(json.dumps({"summary": summary}), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"rows": rows, "summary": summary}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
