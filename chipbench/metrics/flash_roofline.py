"""Share of the flash-attention kernels' roofline: the causal FLOPs the
forward, dq and dkv kernels need (``flops.flash_call_flops``, head_dim
64) ÷ their summed device time ÷ peak bf16 FLOP/s. Their bytes (q, k, v,
o and the gradients, a few MB a call) bound them far below their FLOPs,
so the FLOPs set the roofline. Kernels are found by their function names
in the train step's compiled Pallas calls; each call's sequences follow
from the train-step executions in the trace (every backward layer calls
dq once)."""
from chipbench import flops

KERNELS = {"_flash_kernel": "fwd", "_dq_kernel": "dq", "_dkv_kernel": "dkv"}


def read(ctx):
    info = ctx.out.info
    dims, seq = info["dims"], info["seq_len"]
    work = time = 0.0
    for by_kernel, runs in zip(ctx.kernel_calls("train_step", KERNELS),
                               ctx.executions("train_step")):
        calls = {KERNELS[k]: evs for k, evs in by_kernel.items()}
        if not runs or not calls["dq"]:
            continue
        seqs = len(runs) * dims.n_layers * info["sequences_per_chip"] \
            / len(calls["dq"])
        for kind, evs in calls.items():
            work += len(evs) * flops.flash_call_flops(dims, seq, kind, seqs)
            time += sum(e.dur_ns for e in evs) / 1e9
    if not time:
        return None
    return 100.0 * work / time / ctx.peaks["bf16_flops"]
