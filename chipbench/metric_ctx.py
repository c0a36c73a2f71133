"""What a per-layer reader (``chipbench/metrics/<name>.py``) is given.

A reader is a module with ``read(ctx) -> float | None``. It returns None
when the trace holds nothing for it to read, and never 0 for a share of
a peak. In a cell that ``BENCHMARK.json`` lists the metric for, None ends
the run with no result: the program or kernel the reader looks for has
moved out of its sight.
"""
from __future__ import annotations

import dataclasses
import json

from chipbench import bench
from chipbench import trace as tr


@dataclasses.dataclass
class Context:
    trace: tr.Trace
    out: bench.Outcome          # the driver's programs, counts and sizes
    config: dict                # chipbench/configs/<name>.json
    traffic: dict               # chipbench/workloads/<cell>.json
    chips: int
    peaks: dict                 # chipbench/peaks.json entry of this chip

    def program(self, role: str) -> str | None:
        return self.out.programs.get(role)

    def kernel_calls(self, role: str, kernels) -> list[dict]:
        """Per device, {kernel: calls} of the Pallas kernels named in the
        compiled program playing ``role`` (its HLO text, from the driver,
        says which HLO names each kernel function has)."""
        hlo = self.out.info.get("hlo", {}).get(role)
        if hlo is None:
            return []
        names = tr.pallas_kernels(hlo, kernels)
        return [{k: tr.ops_of(self.trace, d, program=self.program(role),
                              names=names[k]) for k in kernels}
                for d in self.trace.devices]

    def executions(self, role: str) -> list[list[tr.Event]]:
        """Per device, the executions of the program playing ``role``."""
        name = self.program(role)
        if name is None:
            return []
        return [tr.executions(self.trace, d, name)
                for d in self.trace.devices]


def save_expected(ctx: Context, metrics: dict, trace_path: str) -> None:
    """Beside a trace kept with ``--keep-trace``: what its readers need
    besides the trace (the driver's program names and sizes, the Pallas
    calls of the compiled programs) and the values they read, so a test
    can read the trace again and compare."""
    info = {k: v for k, v in ctx.out.info.items()
            if isinstance(v, (int, float, str))}
    info["hlo"] = {role: "\n".join(line for line in text.splitlines()
                                   if "tpu_custom_call" in line)
                   for role, text in ctx.out.info.get("hlo", {}).items()}
    doc = {"programs": ctx.out.programs, "info": info,
           "dims": dataclasses.asdict(ctx.out.info["dims"]),
           "chips": ctx.chips, "device_kind": ctx.peaks["kind"],
           "metrics": {k: v["value"] for k, v in metrics.items()}}
    path = trace_path.replace(".trace.json.gz", "").replace(".json.gz", "")
    with open(path + ".expected.json", "w") as f:
        json.dump(doc, f, indent=1)
