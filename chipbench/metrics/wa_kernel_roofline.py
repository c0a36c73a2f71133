"""Share of the fused window-average kernel's roofline: the bytes it must
move ÷ its device time ÷ peak HBM bandwidth. The fused sync reads the K
replicas, the evicted ring slot and the total, and writes the slot, the
total and W̿: (K+2)·P + 3·P float32. It is bound by bytes, not FLOPs."""
from chipbench import flops

KERNEL = "_wa_sync_fused_kernel"


def read(ctx):
    info = ctx.out.info
    per_call = flops.fused_sync_bytes(info["replicas_per_chip"],
                                      info["padded"])
    moved = time = 0.0
    for by_kernel in ctx.kernel_calls("sync", [KERNEL]):
        evs = by_kernel[KERNEL]
        moved += len(evs) * per_call
        time += sum(e.dur_ns for e in evs) / 1e9
    if not time:
        return None
    return 100.0 * moved / time / ctx.peaks["hbm_bytes_per_s"]
