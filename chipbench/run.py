"""Run one benchmark cell once on the chips of this machine.

  python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
      --trace <0|1>

The cell is an entry of ``BENCHMARK.json``'s ``workloads``; its traffic
(``chipbench/workloads/<cell>.json``) names the driver that runs it
(``chipbench/drivers/<kind>.py``). Set-up (data and weights from the
seed, compiles or compile-cache loads, warm-up, the reads the comparison
needs) ends where the driver opens the window; the window lasts
``--seconds`` and ends on whole units of work that the device has
finished. With ``--trace 0`` the result carries the cell's end-to-end
metrics; with ``--trace 1`` the window is profiled and the result carries
its per-layer metrics, read from the trace by ``chipbench/metrics/``.

After the window the program's state is freed and the plain reference
decides ``correct``. The numbers compared are the last lines on standard
error, each beside its limit; the last line on standard output is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` (and with ``--trace 1`` ``breakdown``), then ``compared``.

Exits 3 with no result when JAX finds no TPU or fewer chips than the
cell asks for, 2 when the program's source is not beside this directory,
and 4 when a metric ``BENCHMARK.json`` lists for the cell has no value:
a reader that finds nothing to read in a cell it is listed for means the
program or kernel it looks for has moved out of its sight.

The compilation cache is the checkout's own ``.jax_cache``, whatever the
environment says, so only the first run in a checkout compiles.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
from chipbench import bench  # noqa: E402


def end_to_end(bench_doc: dict, cell: str, out: bench.Outcome,
               setup_s: float, device: dict) -> dict:
    """The cell's end-to-end metrics: the driver's, with the set-up time
    and the fullest chip's memory peak, which the harness takes."""
    values = dict(out.end_to_end, setup_s=setup_s,
                  peak_hbm_gib=device["memory_peak_bytes"] / 2 ** 30)
    return bench.select(bench.cell_metrics(bench_doc, cell, "end_to_end"),
                        values)


def per_layer(bench_doc: dict, cell: str, ctx) -> dict:
    """The cell's per-layer metrics, each read by its own reader."""
    listed = bench.cell_metrics(bench_doc, cell, "per_layer")
    return bench.select(listed, {
        m["name"]: bench.load_module("metrics", m["name"]).read(ctx)
        for m in listed})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default="",
                    help="with --trace 1, also write the reduced trace "
                         "(gzipped JSON) here, and beside it what the "
                         "readers read from it")
    args = ap.parse_args(argv)

    bench_doc = bench.benchmark()
    entry = bench.cell_entry(bench_doc, args.workload)
    traffic = bench.traffic_doc(args.workload)
    config = bench.config_doc(bench_doc, entry["config"])
    src = os.path.join(bench.ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"run.py: no program source at {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    bench.pin_compile_cache()

    import jax
    try:
        devices = bench.require_chips(jax, entry["chips"])
    except bench.NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 3
    from repro.common.compile_cache import use_compile_cache
    cache = use_compile_cache()
    # every program, however quick to compile, comes from the cache after
    # the first run, so set-up does the same work in every later run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    window = bench.Window(trace=bool(args.trace))
    r = bench.Run(cell=entry, config=config, traffic=traffic,
                  seed=args.seed, seconds=args.seconds, devices=devices,
                  window=window, t_start=T0)
    r.log(f"[run] {args.workload} seed {args.seed} on {len(devices)} x "
          f"{devices[0].device_kind}; compile cache {cache}")
    r.phase("chips found")
    try:
        driver = bench.load_module("drivers", traffic["driver"])
        out = driver.run(r)
        setup_s = r.t_window - T0
        device = bench.device_info(devices)
        out.free()
        r.log(f"[run] set-up {setup_s:.3f}s, window {window.seconds:.3f}s, "
              f"{out.tokens} tokens, peak "
              f"{device['memory_peak_bytes'] / 2 ** 30:.3f} GiB")
        t_ref = time.perf_counter()
        out.verify()
        r.log(f"[run] reference comparison {time.perf_counter() - t_ref:.1f}s")
        breakdown = None
        if args.trace:
            from chipbench import metric_ctx
            from chipbench import trace as tr
            t_tr = time.perf_counter()
            trace = tr.load(window.xplane(), window.seconds * 1e9)
            ctx = metric_ctx.Context(trace=trace, out=out, config=config,
                                     traffic=traffic, chips=len(devices),
                                     peaks=bench.peaks(devices[0].device_kind))
            metrics = per_layer(bench_doc, args.workload, ctx)
            if args.keep_trace:
                tr.save(trace, args.keep_trace)
                metric_ctx.save_expected(ctx, metrics, args.keep_trace)
            busy = [tr.busy_ns(trace, d) for d in trace.devices]
            device["busy_s"] = sum(busy) / len(busy) / 1e9
            device["window_s"] = window.seconds
            breakdown = {"device_ops": tr.top_ops(trace),
                         "idle_gaps": tr.idle_gaps(trace)}
            r.log(f"[run] trace reduction {time.perf_counter() - t_tr:.1f}s")
        else:
            metrics = end_to_end(bench_doc, args.workload, out, setup_s,
                                 device)
    except bench.MissingMetric as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 4
    finally:
        window.cleanup()
    correct = bool(r.checks) and all(c.ok for c in r.checks) \
        and out.failed == 0
    for line in bench.checks_text(r.checks):
        print(line, file=sys.stderr, flush=True)
    print(bench.result_line(correct, out.attempted, out.failed, metrics,
                            device, breakdown, r.checks), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
