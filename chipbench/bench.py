"""The harness's shared parts: where the benchmark's files are, how a cell
and its configuration load, the device check, the measured window and
the result line.

A cell (``workloads/<name>.json``) names its configuration
(``configs/<name>.json``) and its driver (``drivers/<kind>.py``); a
per-layer metric is a reader in ``metrics/<name>.py``. All four are
found by name, so a later cell, configuration, driver or metric is a new
file and no edit here.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Any, Callable

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    return read_json(os.path.join(root, "BENCHMARK.json"))


def cell_entry(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config_doc(bench: dict, name: str, root: str = ROOT) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return read_json(os.path.join(root, c["file"]))
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def traffic_doc(name: str, here: str = HERE) -> dict:
    return read_json(os.path.join(here, "workloads", f"{name}.json"))


def load_module(kind: str, name: str, here: str = HERE):
    """``<here>/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = os.path.join(here, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind[:-1]} {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(bench: dict, cell: str, section: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics a cell reports: those
    whose ``workloads`` name it; without that key, every end-to-end
    metric, and every per-layer metric whose ``moves`` the cell reports."""
    if section == "end_to_end":
        return [m for m in bench[section]
                if cell in m.get("workloads", [cell])]
    reported = {m["name"] for m in cell_metrics(bench, cell, "end_to_end")}
    return [m for m in bench[section]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in reported)]


def peaks(device_kind: str, here: str = HERE) -> dict:
    """Published peaks of one chip of ``device_kind``; an unknown kind is
    an error, never a default."""
    table = read_json(os.path.join(here, "peaks.json"))["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"chipbench/peaks.json")
    return table[device_kind]


def pin_compile_cache(root: str = ROOT) -> str:
    """Keep JAX's persistent compilation cache at the checkout's fixed
    ``.jax_cache`` (the program's own default), whatever the environment
    names: the program takes ``JAX_COMPILATION_CACHE_DIR`` when it is
    set, so the variable is set here, before JAX is imported."""
    path = os.path.join(root, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    return path


def require_chips(jax, n: int):
    """The first ``n`` accelerator devices, or :class:`NoChip`."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform "
                     f"{devices[0].platform!r})")
    if len(devices) < n:
        raise NoChip(f"the cell asks for {n} chips, JAX found "
                     f"{len(devices)}")
    return devices[:n]


@dataclasses.dataclass
class Check:
    """One number compared with its limit; ``ok`` when value <= limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value == self.value and self.value <= self.limit


def checks(numbers: dict, limits: dict) -> list[Check]:
    return [Check(n, float(v), float(limits[n])) for n, v in numbers.items()]


class Window:
    """The measured window: host-clock marks around work the caller has
    finished with ``block_until_ready``; with ``trace`` the JAX profiler
    records exactly this window into a temporary directory."""

    def __init__(self, trace: bool):
        self.trace = trace
        self.t0 = self.t1 = None
        self.trace_dir = tempfile.mkdtemp(prefix="chipbench_trace_") \
            if trace else None

    def open(self):
        if self.trace:
            import jax
            jax.profiler.start_trace(self.trace_dir)
        self.t0 = time.perf_counter()

    def close(self):
        self.t1 = time.perf_counter()
        if self.trace:
            import jax
            jax.profiler.stop_trace()

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    def xplane(self) -> str | None:
        if not self.trace:
            return None
        for dirpath, _, files in os.walk(self.trace_dir):
            for f in files:
                if f.endswith(".xplane.pb"):
                    return os.path.join(dirpath, f)
        return None

    def cleanup(self):
        if self.trace_dir:
            shutil.rmtree(self.trace_dir, ignore_errors=True)


@dataclasses.dataclass
class Run:
    """What a driver gets: the cell, its configuration and traffic, the
    seed and window length, the devices, and where to put its results."""
    cell: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    devices: list
    window: Window
    checks: list = dataclasses.field(default_factory=list)
    t_window: float | None = None      # set-up ends here
    t_start: float = dataclasses.field(default_factory=time.perf_counter)
    log: Callable[[str], None] = lambda m: print(m, file=sys.stderr,
                                                 flush=True)

    def phase(self, what: str) -> None:
        """Log how far into set-up ``what`` ends."""
        self.log(f"[phase] {what} {time.perf_counter() - self.t_start:.3f}s")


@dataclasses.dataclass
class Outcome:
    """What a driver hands back. ``end_to_end`` holds the end-to-end
    values the driver measured over the window (the harness adds the
    set-up time and the memory peak); ``programs`` names the compiled
    programs the trace readers look for by role (``train_step``,
    ``sync``, ...); ``free`` drops the program's state and ``verify``
    then runs the comparison."""
    tokens: int
    attempted: int
    failed: int
    programs: dict
    free: Callable[[], None]
    verify: Callable[[], None]
    info: dict = dataclasses.field(default_factory=dict)
    end_to_end: dict = dataclasses.field(default_factory=dict)


class MissingMetric(RuntimeError):
    """A metric the cell lists was not measured: no result is printed."""


def select(listed: list[dict], values: dict) -> dict:
    """The result line's ``metrics``: every metric listed for the cell,
    with its value; one without a value is :class:`MissingMetric`."""
    missing = [m["name"] for m in listed if values.get(m["name"]) is None]
    if missing:
        raise MissingMetric(f"no value for {', '.join(missing)}")
    return {m["name"]: {"value": float(values[m["name"]]),
                        "unit": m["unit"]} for m in listed}


def device_info(devices) -> dict:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def checks_text(checks: list[Check]) -> list[str]:
    return [f"{c.name} {c.value!r} limit {c.limit!r}"
            f"{'' if c.ok else ' FAILED'}" for c in checks]


def result_line(correct: bool, attempted: int, failed: int, metrics: dict,
                device: dict, breakdown: dict | None,
                checks: list[Check]) -> str:
    out: dict[str, Any] = {"correct": correct, "attempted": attempted,
                           "failed": failed, "metrics": metrics,
                           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["compared"] = {c.name: {"value": c.value, "limit": c.limit}
                       for c in checks}
    return json.dumps(out)
