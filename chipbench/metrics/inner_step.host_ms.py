"""Host time of one inner step: the mean duration of the program's
``hwa.inner_step`` spans, in which ``Trainer.run`` builds and enqueues
the step. Beside ``inner_step.device_ms`` it shows the host's headroom."""

SPAN = "hwa.inner_step"


def read(ctx):
    durs = [dur for name, _, dur in ctx.trace.host if name == SPAN]
    if not durs:
        return None
    return sum(durs) / len(durs) / 1e6
