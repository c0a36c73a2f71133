"""tools/bench_check.py regression guard tests — synthetic bench /
thresholds pairs exercising the hardened failure modes: a renamed bench
block dangling its thresholds, an unknown (misspelled) thresholds
section silently un-guarding its checks, and the unguarded-block
coverage warning.
"""
import importlib.util
import json
import os
import sys

import pytest

_TOOLS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools")


@pytest.fixture(scope="module")
def bench_check():
    spec = importlib.util.spec_from_file_location(
        "bench_check", os.path.join(_TOOLS, "bench_check.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _write(tmp_path, name, obj):
    p = tmp_path / name
    p.write_text(json.dumps(obj))
    return str(p)


_BENCH = {"sync_fused": {"launches": 1, "us": 12.5},
          "sync/tree": {"pod_bytes": 0}}
_TH = {"_comment": "test", "required": ["sync_fused.us"],
       "bounds": {"sync_fused.launches": {"min": 1, "max": 1},
                  "sync/tree.pod_bytes": {"max": 0}}}


def test_clean_pass(bench_check, tmp_path):
    rc = bench_check.run(_write(tmp_path, "b.json", _BENCH),
                         _write(tmp_path, "t.json", _TH),
                         log=lambda *_: None)
    assert rc == 0


def test_renamed_block_fails_and_warns(bench_check, tmp_path):
    # the rename drops the guarded keys AND leaves the new block bare
    bench = {"sync_fused_v2": {"launches": 2, "us": 12.5},
             "sync/tree": {"pod_bytes": 0}}
    out = []
    rc = bench_check.run(_write(tmp_path, "b.json", bench),
                         _write(tmp_path, "t.json", _TH), log=out.append)
    assert rc == 1
    text = "\n".join(out)
    assert "missing required metric: sync_fused.us" in text
    assert "missing bounded metric: sync_fused.launches" in text
    assert "'sync_fused_v2' has no threshold" in text


def test_unknown_section_fails(bench_check, tmp_path):
    # a misspelled section would silently skip every check inside it
    th = {"requried": ["sync_fused.us"],
          "bounds": {"sync_fused.launches": {"max": 1},
                     "sync/tree.pod_bytes": {"max": 0},
                     "sync_fused.us": {"min": 0}}}
    out = []
    rc = bench_check.run(_write(tmp_path, "b.json", _BENCH),
                         _write(tmp_path, "t.json", th), log=out.append)
    assert rc == 1
    assert any("unknown thresholds section 'requried'" in ln
               for ln in out)


def test_bounds_violation_and_dotted_keys(bench_check, tmp_path):
    bench = {"sync_fused": {"launches": 3, "us": 1.0},
             "sync/tree": {"pod_bytes": 64}}
    out = []
    rc = bench_check.run(_write(tmp_path, "b.json", bench),
                         _write(tmp_path, "t.json", _TH), log=out.append)
    assert rc == 1
    text = "\n".join(out)
    assert "sync_fused.launches = 3 > max 1" in text
    # literal dotted/slashed block names resolve greedily
    assert "sync/tree.pod_bytes = 64 > max 0" in text


def test_unguarded_block_warns_but_passes(bench_check, tmp_path):
    bench = dict(_BENCH, new_bench={"us": 5.0})
    out = []
    rc = bench_check.run(_write(tmp_path, "b.json", bench),
                         _write(tmp_path, "t.json", _TH), log=out.append)
    assert rc == 0
    assert any("'new_bench' has no threshold" in ln for ln in out)


def test_real_repo_files_pass(bench_check):
    # the committed trajectory must satisfy the committed thresholds
    # with zero unguarded blocks (full schema coverage)
    out = []
    assert bench_check.run(log=out.append) == 0
    assert not any("warn:" in ln for ln in out)


def test_unreadable_bench_fails(bench_check, tmp_path):
    p = tmp_path / "broken.json"
    p.write_text("{not json")
    rc = bench_check.run(str(p), _write(tmp_path, "t.json", _TH),
                         log=lambda *_: None)
    assert rc == 1


def test_failed_worker_fails_the_run(tmp_path):
    """A mesh-benchmark worker that dies fails the benchmark run (it used
    to print an ERROR row and return {}, so the run exited 0)."""
    root = os.path.dirname(_TOOLS)
    sys.path.insert(0, root)
    try:
        from benchmarks.common import run_forced_device_worker
    finally:
        sys.path.remove(root)
    worker = tmp_path / "worker.py"
    worker.write_text("import os, sys\n"
                      "assert os.environ['JAX_PLATFORMS'] == 'cpu'\n"
                      "sys.exit(3)\n")
    rows = []
    with pytest.raises(RuntimeError, match="exited 3"):
        run_forced_device_worker(str(worker), "--work", error_row="x/ERROR",
                                 print_fn=rows.append, timeout=60)
    assert rows and rows[0].startswith("x/ERROR,")
