"""Every file of the benchmark loads and names what exists; a cell added
as files is found without edits; BENCHMARK.json keeps to its limits."""
import json
import os
import re
import shutil
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))                    # the repository root
from chipbench import bench

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
WIDTH = re.compile(r"(hidden_size|intermediate|latent|state_size|_proj|"
                   r"head_size|_dim$|_rank$|expansion|experts_per_tok)")


@pytest.fixture(scope="module")
def doc():
    return bench.benchmark()


def test_top_level_keys(doc):
    assert list(doc) == ["command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"]
    assert doc["command"] == ["python3", "chipbench/run.py"]
    for p in doc["paths"]:
        assert os.path.isdir(os.path.join(bench.ROOT, p))
    assert len(json.dumps(doc)) < 64 * 1024


def test_run_seconds_fit_a_full_check(doc):
    cells, s = 24, doc["run_seconds"]
    assert 1 <= s <= 51
    assert (2 + 14 * cells) * (s + 60) + cells * 2 * 90 + 1200 <= 43200


def test_configs(doc):
    for c in doc["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"])
        assert 1 <= len(c["why"]) <= 200 and "\n" not in c["why"]
        assert c["file"].startswith("chipbench/configs/")
        cfg = bench.read_json(os.path.join(bench.ROOT, c["file"]))
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        for key in c["reduced"]:
            assert NAME.match(key) and not WIDTH.search(key), key
            assert key in cfg and key in cfg["published"], key
        assert any(w["config"] == c["name"] for w in doc["workloads"])


def test_workloads(doc):
    names = {c["name"] for c in doc["configs"]}
    for w in doc["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in names and w["chips"] in (1, 4)
        assert w["name"] == f"{w['config']}.{w['traffic']}"
        assert 1 <= len(w["why"]) <= 200
        traffic = bench.traffic_doc(w["name"])
        driver = bench.load_module("drivers", traffic["driver"])
        assert callable(driver.run)
        assert traffic["limits"], w["name"]
    assert sum(w["chips"] == 4 for w in doc["workloads"]) <= max(
        1, len(doc["workloads"]) // 2)


def test_metrics(doc):
    e2e = {m["name"] for m in doc["end_to_end"]}
    cells = {w["name"] for w in doc["workloads"]}
    assert "setup_s" in e2e
    for m in doc["end_to_end"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in doc["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["moves"] in e2e and m["layer"]
        assert set(m.get("workloads", cells)) <= cells
        assert callable(bench.load_module("metrics", m["name"]).read)
    for cell in cells:
        assert bench.cell_metrics(doc, cell, "per_layer")


def test_a_cell_added_as_files_is_found(doc, tmp_path):
    here = tmp_path / "chipbench"
    for sub in ("workloads", "drivers", "metrics"):
        (here / sub).mkdir(parents=True)
    traffic = dict(bench.traffic_doc(doc["workloads"][0]["name"]),
                   driver="copy")
    (here / "workloads" / "model-x.mix.json").write_text(json.dumps(traffic))
    first = bench.traffic_doc(doc["workloads"][0]["name"])["driver"]
    shutil.copy(os.path.join(bench.HERE, "drivers", f"{first}.py"),
                here / "drivers" / "copy.py")
    (here / "metrics" / "x.count.py").write_text(
        "def read(ctx):\n    return None\n")
    got = bench.traffic_doc("model-x.mix", here=str(here))
    assert got["driver"] == "copy"
    assert callable(bench.load_module("drivers", "copy", here=str(here)).run)
    assert bench.load_module("metrics", "x.count",
                             here=str(here)).read(None) is None
    extended = dict(doc, per_layer=doc["per_layer"] + [
        {"name": "x.count", "workloads": ["model-x.mix"]}])
    assert [m["name"] for m in bench.cell_metrics(
        extended, "model-x.mix", "per_layer")][-1] == "x.count"


def test_unknown_device_kind_is_an_error():
    assert bench.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        bench.peaks("TPU v9 imaginary")


def test_a_per_layer_metric_follows_what_it_moves():
    doc = {"end_to_end": [
        {"name": "setup_s"}, {"name": "rate"},
        {"name": "ttft", "workloads": ["serve"]}],
        "per_layer": [{"name": "mfu", "moves": "rate"},
                      {"name": "queue", "moves": "ttft"},
                      {"name": "only", "moves": "rate",
                       "workloads": ["other"]}]}
    assert [m["name"] for m in bench.cell_metrics(
        doc, "train", "end_to_end")] == ["setup_s", "rate"]
    assert [m["name"] for m in bench.cell_metrics(
        doc, "train", "per_layer")] == ["mfu"]
    assert [m["name"] for m in bench.cell_metrics(
        doc, "serve", "per_layer")] == ["mfu", "queue"]
    assert [m["name"] for m in bench.cell_metrics(
        doc, "other", "per_layer")] == ["mfu", "only"]


def test_a_listed_metric_without_a_value_ends_the_run(doc):
    cell = doc["workloads"][0]["name"]
    listed = bench.cell_metrics(doc, cell, "per_layer")
    values = {m["name"]: 1.5 for m in listed}
    assert set(bench.select(listed, values)) == set(values)
    values[listed[-1]["name"]] = None
    with pytest.raises(bench.MissingMetric, match=listed[-1]["name"]):
        bench.select(listed, values)


def test_the_compile_cache_is_the_checkouts_own(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    path = bench.pin_compile_cache(str(tmp_path))
    assert path == str(tmp_path / ".jax_cache")
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == path
