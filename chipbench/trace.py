"""Reduction of a JAX profiler trace (``.xplane.pb``) to device events.

Only the profiler's own device events are read: the compiled programs on
each TPU's ``XLA Modules`` line, named after the jitted function
(``jit_hwa_step``), and the operations on its ``XLA Ops`` line, each with
the program it belongs to. Host threads are read only for what the host
was doing while the device sat idle.

:func:`load` turns a trace file into a :class:`Trace` of plain tuples,
which is also what ``chipbench/testdata`` keeps (``save``/``load_json``),
so the per-layer readers are tested on a trace recorded on the chip.
"""
from __future__ import annotations

import dataclasses
import gzip
import json
from typing import Iterable

DEVICE_PREFIX = "/device:TPU:"
MODULE_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"


@dataclasses.dataclass(frozen=True)
class Event:
    start_ns: float
    dur_ns: float
    name: str            # a program's name, or an operation's HLO name
    program: str         # the compiled program (module) the event is in
    opcode: str = ""     # an operation's HLO opcode


    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Trace:
    """Per device: program executions and operations, both sorted by
    start. ``host`` holds the host threads' named spans (name, start,
    duration) for attributing idle gaps; ``window_ns`` is the traced
    window's length by the host clock."""
    modules: dict[str, list[Event]]
    ops: dict[str, list[Event]]
    host: list[tuple[str, float, float]]
    window_ns: float

    @property
    def devices(self) -> list[str]:
        return sorted(self.modules)


class EmptyTrace(ValueError):
    """The profiler window holds no device events."""


def _program(name: str) -> str:
    """``jit_hwa_step(1234)`` on the modules line: drop the program id."""
    return name.split("(")[0] if name.endswith(")") else name


def parse_op(text: str) -> tuple[str, str]:
    """(name, opcode) of an ``XLA Ops`` event, whose name is the HLO
    instruction: ``%fusion.3 = f32[8]{0} fusion(...), kind=...``."""
    head, sep, rest = text.partition(" = ")
    if not sep:
        return text, ""
    name = head.strip().removeprefix("ROOT ").lstrip("%")
    depth, i = 0, 0
    if rest.startswith("("):                 # a tuple shape
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        rest = rest[i + 1:]
    else:
        rest = rest.partition(" ")[2]
    return name, rest.strip().partition("(")[0]


def _owned(ops: list[Event], modules: list[Event]) -> list[Event]:
    """Each operation tagged with the program execution it ran in."""
    out, j = [], 0
    for op in ops:
        while j < len(modules) and modules[j].end_ns < op.start_ns:
            j += 1
        prog = modules[j].program if j < len(modules) and \
            modules[j].start_ns <= op.start_ns else ""
        out.append(dataclasses.replace(op, program=prog))
    return out


def load(path: str, window_ns: float) -> Trace:
    """Read a ``.xplane.pb`` file recorded by ``jax.profiler``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    modules: dict[str, list[Event]] = {}
    ops: dict[str, list[Event]] = {}
    host: list[tuple[str, float, float]] = []
    starts = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            dev = plane.name[len("/device:"):]
            for line in plane.lines:
                if line.name not in (MODULE_LINE, OPS_LINE):
                    continue
                is_mod = line.name == MODULE_LINE
                out = (modules if is_mod else ops).setdefault(dev, [])
                for ev in line.events:
                    if is_mod:
                        e = Event(float(ev.start_ns), float(ev.duration_ns),
                                  ev.name, _program(ev.name))
                    else:
                        name, opcode = parse_op(ev.name)
                        e = Event(float(ev.start_ns), float(ev.duration_ns),
                                  name, "", opcode)
                    out.append(e)
                    starts.append(e.start_ns)
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.duration_ns > 0:
                        host.append((ev.name, float(ev.start_ns),
                                     float(ev.duration_ns)))
    if not starts:
        raise EmptyTrace(f"no device events in {path}")
    for evs in list(modules.values()) + list(ops.values()):
        evs.sort(key=lambda e: e.start_ns)
    for dev in ops:
        ops[dev] = _owned(ops[dev], modules.get(dev, []))
    host.sort(key=lambda h: h[1])
    return Trace(modules=modules, ops=ops, host=host, window_ns=window_ns)


# ------------------------------------------------------------ reduction


def union_ns(events: Iterable[Event]) -> float:
    """Length of the union of the events' intervals."""
    total, end = 0.0, float("-inf")
    for e in sorted(events, key=lambda e: e.start_ns):
        if e.start_ns >= end:
            total += e.dur_ns
            end = e.end_ns
        elif e.end_ns > end:
            total += e.end_ns - end
            end = e.end_ns
    return total


def busy_ns(trace: Trace, device: str) -> float:
    """Time in which an operation ran on ``device``: the union of its
    program executions (programs hold every operation)."""
    return union_ns(trace.modules.get(device, []))


def executions(trace: Trace, device: str, program: str) -> list[Event]:
    return [e for e in trace.modules.get(device, []) if e.program == program]


def base_name(op: str) -> str:
    """An operation's name without XLA's ``.<n>`` instance suffix:
    ``_flash_kernel.3`` -> ``_flash_kernel``."""
    head, dot, tail = op.rpartition(".")
    return head if dot and tail.isdigit() else op


def ops_of(trace: Trace, device: str, *, program: str | None = None,
           names: Iterable[str] | None = None) -> list[Event]:
    """Operations on ``device``, optionally only those of ``program`` or
    those whose HLO name is one of ``names``."""
    names = set(names) if names is not None else None
    return [e for e in trace.ops.get(device, [])
            if (program is None or e.program == program)
            and (names is None or e.name in names)]


def pallas_kernels(hlo_text: str, kernels: Iterable[str]) -> dict:
    """HLO names of a compiled program's Pallas calls, by kernel: each
    ``tpu_custom_call``'s serialized Mosaic body holds the name of the
    kernel function it compiles. Returns {kernel: [hlo names]}."""
    import base64
    import re
    kernels = list(kernels)
    found: dict[str, list[str]] = {k: [] for k in kernels}
    for line in hlo_text.splitlines():
        if "tpu_custom_call" not in line or " = " not in line:
            continue
        m = re.search(r'"body":"([^"]+)"', line)
        if not m:
            continue
        body = base64.b64decode(m.group(1))
        name = parse_op(line.strip())[0]
        hits = [k for k in kernels if k.encode() in body]
        if len(hits) == 1:
            found[hits[0]].append(name)
    return found


def top_ops(trace: Trace, n: int = 10) -> list[list]:
    """The ``n`` operations that took most device time, summed over
    devices and averaged per device: [[name, seconds], ...]."""
    tot: dict[str, float] = {}
    for evs in trace.ops.values():
        for e in evs:
            key = f"{e.program}/{e.name} ({e.opcode})"
            tot[key] = tot.get(key, 0.0) + e.dur_ns
    nd = max(len(trace.ops), 1)
    top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / nd / 1e9] for k, v in top]


def idle_gaps(trace: Trace, n: int = 10) -> list[list]:
    """The ``n`` longest gaps between program executions on the first
    device, each named by the longest host span that overlaps it."""
    dev = trace.devices[0]
    evs = trace.modules[dev]
    gaps = []
    end = evs[0].end_ns
    for e in evs[1:]:
        if e.start_ns > end:
            gaps.append((end, e.start_ns))
        end = max(end, e.end_ns)
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for a, b in gaps[:n]:
        best, what = 0.0, "no host span"
        for name, s, d in trace.host:
            ov = min(b, s + d) - max(a, s)
            if ov > best:
                best, what = ov, name
        out.append([f"idle during {what}", (b - a) / 1e9])
    return out


# ------------------------------------------------------------ storage


def save(trace: Trace, path: str, programs: Iterable[str] | None = None,
         ops: Iterable[str] | None = None) -> None:
    """Write the trace as gzipped JSON, keeping only the programs and
    operation names given (all when None)."""
    keep_p = set(programs) if programs is not None else None
    keep_o = set(ops) if ops is not None else None

    def rows(evs, keep, by):
        return [[e.start_ns, e.dur_ns, e.name, e.program, e.opcode]
                for e in evs if keep is None or getattr(e, by) in keep]

    doc = {"window_ns": trace.window_ns,
           "modules": {d: rows(v, keep_p, "program")
                       for d, v in trace.modules.items()},
           "ops": {d: rows(v, keep_o, "name") for d, v in trace.ops.items()},
           "host": [list(h) for h in trace.host[:2000]]}
    with gzip.open(path, "wt") as f:
        json.dump(doc, f)


def load_json(path: str) -> Trace:
    with gzip.open(path, "rt") as f:
        doc = json.load(f)
    ev = lambda rows: [Event(float(r[0]), float(r[1]), *r[2:]) for r in rows]
    trace = Trace(modules={k: ev(v) for k, v in doc["modules"].items()},
                  ops={k: ev(v) for k, v in doc["ops"].items()},
                  host=[tuple(h) for h in doc["host"]],
                  window_ns=float(doc["window_ns"]))
    if not any(trace.modules.values()):
        raise EmptyTrace(f"no device events in {path}")
    return trace
