"""Device time of one execution of the train-step program (all replicas
of the chip), the mean over executions and chips."""


def read(ctx):
    runs = [e for dev in ctx.executions("train_step") for e in dev]
    if not runs:
        return None
    return sum(e.dur_ns for e in runs) / len(runs) / 1e6
