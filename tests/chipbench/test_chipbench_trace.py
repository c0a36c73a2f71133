"""Trace reduction and the per-layer readers, on a synthetic trace and on
traces recorded on the chip (``chipbench/testdata``)."""
import base64
import glob
import gzip
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))                    # the repository root
from chipbench import bench, metric_ctx
from chipbench import trace as tr
from chipbench.reference.granite import Dims

TESTDATA = os.path.join(bench.HERE, "testdata")


def ev(start, dur, name, program):
    return tr.Event(float(start), float(dur), name, program)


def test_union_counts_overlaps_once():
    evs = [ev(0, 10, "a", "p"), ev(5, 10, "b", "p"), ev(20, 5, "c", "p")]
    assert tr.union_ns(evs) == 20.0
    assert tr.base_name("_flash_kernel.12") == "_flash_kernel"
    assert tr.base_name("all-reduce") == "all-reduce"
    assert tr.parse_op("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %p), "
                       "kind=kLoop") == ("fusion.3", "fusion")
    assert tr.parse_op("%all-reduce-start.1 = (f32[8]{0}, f32[8]{0}) "
                       "all-reduce-start(f32[8]{0} %x)") == \
        ("all-reduce-start.1", "all-reduce-start")


def test_a_window_without_device_events_is_an_error(tmp_path):
    path = tmp_path / "empty.json.gz"
    with gzip.open(path, "wt") as f:
        json.dump({"window_ns": 1e9, "modules": {}, "ops": {}, "host": []},
                  f)
    with pytest.raises(tr.EmptyTrace):
        tr.load_json(str(path))


def custom_call(name, kernel):
    body = base64.b64encode(f"mosaic {kernel} body".encode()).decode()
    return (f'  %{name} = (bf16[2,4]{{1,0}}) custom-call(bf16[2,4]{{1,0}} '
            f'%p), custom_call_target="tpu_custom_call", backend_config='
            f'{{"custom_call_config": {{"body":"{body}"}}}}')


def synthetic_ctx():
    dims = Dims(vocab=49155, d_model=2048, n_layers=1, n_heads=32,
                n_kv_heads=8, head_dim=64, d_ff=8192, eps=1e-6,
                rope_theta=1e4, dtype="bfloat16")
    dev = "TPU:0"
    modules = [ev(0, 100e6, "jit_hwa_step(1)", "jit_hwa_step"),
               ev(110e6, 100e6, "jit_hwa_step(1)", "jit_hwa_step"),
               ev(220e6, 50e6, "jit_sync_step(2)", "jit_sync_step")]
    ops = [ev(10e6, 2e6, "closed_call.4", "jit_hwa_step"),
           ev(20e6, 3e6, "custom-call.7", "jit_hwa_step"),
           ev(30e6, 4e6, "checkpoint.2", "jit_hwa_step"),
           ev(40e6, 1e6, "fusion.3", "jit_hwa_step"),
           ev(230e6, 10e6, "custom-call.1", "jit_sync_step")]
    hlo = {"train_step": "\n".join([
        custom_call("closed_call.4", "_flash_kernel"),
        custom_call("custom-call.7", "_dq_kernel"),
        custom_call("checkpoint.2", "_dkv_kernel")]),
        "sync": custom_call("custom-call.1", "_wa_sync_fused_kernel")}
    trace = tr.Trace(modules={dev: modules}, ops={dev: ops}, host=[],
                     window_ns=300e6)
    out = bench.Outcome(tokens=0, attempted=0, failed=0,
                        programs={"train_step": "jit_hwa_step",
                                  "sync": "jit_sync_step"},
                        free=None, verify=None,
                        info={"dims": dims, "seq_len": 1024,
                              "tokens_per_step": 8192, "replicas": 2,
                              "replicas_per_chip": 2,
                              "sequences_per_chip": 8,
                              "padded": 262_168_576, "hlo": hlo})
    return metric_ctx.Context(trace=trace, out=out, config={}, traffic={},
                              chips=1, peaks=bench.peaks("TPU v5 lite"))


def read(name, ctx):
    return bench.load_module("metrics", name).read(ctx)


def test_readers_on_a_synthetic_trace():
    ctx = synthetic_ctx()
    assert read("device.idle_share", ctx) == pytest.approx(
        100 * (1 - 250 / 300))
    assert read("inner_step.device_ms", ctx) == pytest.approx(100.0)
    assert read("sync.device_ms", ctx) == pytest.approx(50.0)
    mfu = 2 * 8192 / 0.3 * 981_516_288 / 197e12 * 100
    assert read("train_mfu", ctx) == pytest.approx(mfu)
    # one dq call per layer per step: 2 steps of 8 sequences -> 16 per call
    m = 32 * 64 * 1024 * 1025
    flash = (2 + 3 + 4) * m * 16 / 9e-3 / 197e12 * 100
    assert read("flash_roofline", ctx) == pytest.approx(flash)
    wa = (4 * 262_168_576 + 3 * 262_168_576) * 4 / 10e-3 / 819e9 * 100
    assert read("wa_kernel_roofline", ctx) == pytest.approx(wa)


@pytest.mark.parametrize("name", sorted(
    os.path.basename(p) for p in glob.glob(
        os.path.join(TESTDATA, "*.trace.json.gz"))))
def test_readers_on_a_trace_recorded_on_the_chip(name):
    """Each recorded trace comes with the values its readers gave on the
    chip (``<name>.expected.json``): the readers, the trace's programs
    and counts, and the metrics, each at most 100 % where it is a share."""
    path = os.path.join(TESTDATA, name)
    trace = tr.load_json(path)
    want = bench.read_json(path.replace(".trace.json.gz", ".expected.json"))
    dims = Dims(**want["dims"])
    out = bench.Outcome(tokens=0, attempted=0, failed=0,
                        programs=want["programs"], free=None,
                        verify=None, info=dict(want["info"], dims=dims))
    ctx = metric_ctx.Context(trace=trace, out=out, config={}, traffic={},
                             chips=want["chips"],
                             peaks=bench.peaks(want["device_kind"]))
    assert want["metrics"]
    for name, value in want["metrics"].items():
        got = read(name, ctx)
        assert got == pytest.approx(value, rel=1e-9), name
        if name.endswith("roofline") or "mfu" in name or "share" in name:
            assert 0 < got <= 100, (name, got)
