"""Device time of one W̿ evaluation: the executions of the evaluation
program that start inside a ``hwa.evaluate`` span, summed per span; the
mean over spans and chips. A span in which no evaluation ran on a chip
is left out there."""

SPAN = "hwa.evaluate"


def read(ctx):
    spans = [(s, s + d) for name, s, d in ctx.trace.host if name == SPAN]
    per_span = []
    for runs in ctx.executions("eval"):
        for a, b in spans:
            inside = [e.dur_ns for e in runs if a <= e.start_ns < b]
            if inside:
                per_span.append(sum(inside))
    if not per_span:
        return None
    return sum(per_span) / len(per_span) / 1e6
