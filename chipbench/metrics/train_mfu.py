"""Model FLOP utilization of the traced window: tokens trained per second
(train-step executions in the trace × tokens per step ÷ window) × model
FLOPs per token (``flops.model_flops_per_token``) ÷ (chips × peak bf16
FLOP/s). Counts the syncs, the W̿ evaluations and the idle time in the
window against the step."""
from chipbench import flops


def read(ctx):
    runs = ctx.executions("train_step")
    if not runs or not runs[0] or ctx.trace.window_ns <= 0:
        return None
    info = ctx.out.info
    tokens = len(runs[0]) * info["tokens_per_step"]
    rate = tokens / (ctx.trace.window_ns / 1e9)
    per_token = flops.model_flops_per_token(info["dims"], info["seq_len"])
    return 100.0 * rate * per_token / (ctx.chips * ctx.peaks["bf16_flops"])
