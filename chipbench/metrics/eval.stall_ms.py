"""The device's idle time that one W̿ evaluation causes: for each
``hwa.evaluate`` span, the time from the span's start to the start of
the first train-step execution after the span ends in which no program
ran. The evaluation's blocking reads drain the device's queue, and the
device then waits for the host to dispatch again. The mean over spans
and chips; a span with no later train step in the window is left out."""
import dataclasses

from chipbench import trace as tr

SPAN = "hwa.evaluate"


def idle_ns(runs, a: float, b: float) -> float:
    """Time in [a, b) in which none of ``runs`` ran."""
    clipped = []
    for e in runs:
        start, end = max(e.start_ns, a), min(e.end_ns, b)
        if end > start:
            clipped.append(dataclasses.replace(e, start_ns=start,
                                               dur_ns=end - start))
    return (b - a) - tr.union_ns(clipped)


def read(ctx):
    spans = [(s, s + d) for name, s, d in ctx.trace.host if name == SPAN]
    train = ctx.program("train_step")
    stalls = []
    for dev in ctx.trace.devices:
        runs = ctx.trace.modules[dev]
        for a, b in spans:
            nxt = [e.start_ns for e in runs
                   if e.program == train and e.start_ns >= b]
            if nxt:
                stalls.append(idle_ns(runs, a, min(nxt)))
    if not stalls:
        return None
    return sum(stalls) / len(stalls) / 1e6
