"""The readers of the program's own profiler spans (``hwa.*`` on the
trace's host plane), on a synthetic trace with hand-placed spans and
executions. The traces recorded on the chip keep too few host events to
hold the spans."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))                    # the repository root
from chipbench import bench, metric_ctx
from chipbench import trace as tr

MS = 1e6
READERS = ("inner_step.host_ms", "eval.device_ms", "eval.stall_ms")


def ev(start_ms, dur_ms, program):
    return tr.Event(start_ms * MS, dur_ms * MS, f"{program}(1)", program)


def span(name, start_ms, dur_ms):
    return (name, start_ms * MS, dur_ms * MS)


def ctx_of(modules, host, devices=1):
    trace = tr.Trace(modules={f"TPU:{d}": list(modules)
                              for d in range(devices)},
                     ops={}, host=sorted(host, key=lambda h: h[1]),
                     window_ns=1000 * MS)
    out = bench.Outcome(tokens=0, attempted=0, failed=0,
                        programs={"train_step": "jit_hwa_step",
                                  "sync": "jit_sync_step",
                                  "eval": "jit_eval_batch"},
                        free=None, verify=None)
    return metric_ctx.Context(trace=trace, out=out, config={}, traffic={},
                              chips=devices, peaks={})


def read(name, ctx):
    return bench.load_module("metrics", name).read(ctx)


def one_cycle():
    """Two inner steps, a sync, an evaluation of two batches, the next
    step; a second evaluation at the end of the window, with no train
    step after it."""
    modules = [ev(0, 100, "jit_hwa_step"), ev(100, 100, "jit_hwa_step"),
               ev(200, 50, "jit_sync_step"),
               ev(250, 8, "jit_eval_batch"), ev(260, 4, "jit_eval_batch"),
               ev(275, 100, "jit_hwa_step"),
               ev(400, 5, "jit_eval_batch"),
               ev(600, 7, "jit_eval_batch")]      # in no hwa.evaluate span
    host = [span("hwa.step", 0, 2), span("hwa.inner_step", 0.5, 1),
            span("hwa.step", 2, 230), span("hwa.inner_step", 2.5, 3),
            span("hwa.sync", 6, 1),
            span("hwa.evaluate", 220, 50),
            span("$trainer.py:194 record", 220.1, 49.8),
            span("hwa.step", 232, 2), span("hwa.inner_step", 232.5, 0.5),
            span("hwa.evaluate", 390, 20)]
    return modules, host


@pytest.mark.parametrize("devices", [1, 2])
def test_span_readers_on_a_synthetic_trace(devices):
    ctx = ctx_of(*one_cycle(), devices=devices)
    # inner steps of 1, 3 and 0.5 ms on the host
    assert read("inner_step.host_ms", ctx) == pytest.approx(1.5)
    # the first evaluation ran 8 + 4 ms on the device, the second 5 ms
    assert read("eval.device_ms", ctx) == pytest.approx(8.5)
    # from 220 to the next step at 275: the sync to 250, the batches
    # 250-258 and 260-264; idle 258-260 and 264-275. The second span has
    # no train step after it
    assert read("eval.stall_ms", ctx) == pytest.approx(13.0)


def test_span_readers_find_nothing_in_a_trace_without_spans():
    modules, host = one_cycle()
    ctx = ctx_of(modules, [h for h in host if not h[0].startswith("hwa.")])
    assert [read(name, ctx) for name in READERS] == [None, None, None]


def test_span_readers_skip_spans_with_nothing_to_read():
    modules, host = one_cycle()
    no_eval = [e for e in modules if e.program != "jit_eval_batch"]
    assert read("eval.device_ms", ctx_of(no_eval, host)) is None
    late = [h for h in host if h[0] != "hwa.evaluate"] + [
        span("hwa.evaluate", 390, 20)]
    assert read("eval.stall_ms", ctx_of(modules, late)) is None


def test_the_readers_read_spans_the_program_records():
    from repro.train.trainer import SPANS
    for name in READERS:
        assert bench.load_module("metrics", name).SPAN in SPANS, name
