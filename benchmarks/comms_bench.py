"""Compressed WA-state + cross-pod comms numbers (PR 10), measured from
real lowered HLO and real sync outputs on the pod-carved (2,2,2) test
mesh (pod=2, replica=2, model=2 → K=4 as 2 pods × 2 members).

For each precision token (f32 / bf16 / fp8) the worker builds the
two-level outer sync via ``SyncPlan(wa_dtype=tok, comms_dtype=tok)`` and
records:

- **ring HBM**: bytes of the (I, P) window ring in the token's storage
  dtype (+ the fp8 per-ALIGN-block f32 scales), and the ratio vs f32 —
  the WA-state HBM reduction. The ratio is stated on the ring (+scales),
  the (I, P) term that dominates WA state as the window I grows; the f32
  running total and Kahan compensation are (P,) and amortize away.
- **cross-pod payload**: modeled per-device ICI bytes of the collectives
  crossing the pod axis in the compiled HLO (same traffic model as
  ``benchmarks.sync_tree``), and the ratio vs f32. Both compressed
  payloads cross the wire as same-width integer bit-views (bf16→u16
  gather, ~2×; fp8→u8 gather + f32 per-block scales, ~4×) so XLA's
  float-normalization pass cannot widen them back — these are REAL
  compiled wire bytes, not a semantic claim.
- **bounded-ULP parity**: the compressed W̿ against the f32 leg's, in
  relative ULPs of the compressed dtype at the buffer's working scale
  (``repro.common.quant.rel_ulp_error``) — guarded by the per-dtype
  budgets in ``benchmarks/thresholds.json``'s ``ulp_budgets`` section.
  The f32 leg must report exactly 0.0 (bit-identical — the repo-wide
  f32-default guarantee).

``make bench-comms`` runs this module alone; ``benchmarks.run`` merges
the record into BENCH_kernels.json under ``sync/comms``. The
device-hungry part runs in a subprocess so the forced 8-device host
platform never leaks into the benchmark process.
"""
import json
import sys

from benchmarks.common import csv_row

_WORKER_FLAG = "--comms-worker"

TOKENS = ("f32", "bf16", "fp8")


def comms_record() -> dict:
    """Build + compile + RUN the two-level outer sync at each precision
    and extract HBM/payload/parity numbers. Needs ≥8 forced host
    devices."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.analysis.collectives import collective_stats
    from repro.common.quant import rel_ulp_error, wa_dtype
    from repro.configs import get_smoke_config
    from repro.core.hwa import HWAConfig
    from repro.launch.hlo import sync_collective_audit
    from repro.launch.mesh import make_tree_test_mesh
    from repro.launch.steps import (SyncPlan, TwoLevel, build_hwa_bundles,
                                    window_state_args)
    from repro.models.registry import build_model
    from repro.sharding.rules import make_tp_rules

    mesh = make_tree_test_mesh()
    rules = make_tp_rules(mesh, replica_axis=("pod", "replica"))
    lm = build_model(get_smoke_config("granite-3-2b"))
    hwa = HWAConfig(n_replicas=4, window=3, use_kernels=True, outer_every=2)
    topo = TwoLevel("replica", "pod", outer_every=2)

    params = lm.init(jax.random.key(0))
    div = jax.tree.map(
        lambda x: np.asarray(                    # host copy: sync donates
            x[None] + 0.1 * jax.random.normal(jax.random.key(7),
                                              (4,) + x.shape)), params)

    rec = {"mesh": {k: int(v) for k, v in mesh.shape.items()},
           "window": hwa.window}
    for tok in TOKENS:
        plan = SyncPlan(hwa=hwa, topology=topo, wa_dtype=tok,
                        comms_dtype=tok)
        sync = build_hwa_bundles(lm, rules, plan).sync
        spec = sync.pack_spec
        compiled = sync.lower(mesh).compile()
        audit = sync_collective_audit(compiled.as_text(), mesh,
                                      "replica", "pod")
        pod_text = "\n".join(line for _, line in audit["outer"])
        pod_bytes = collective_stats(pod_text).traffic_bytes

        itemsize = np.dtype(wa_dtype(tok)).itemsize
        ring_bytes = hwa.window * spec.padded * itemsize
        scale_bytes = (hwa.window * (spec.padded // spec.align) * 4
                       if tok == "fp8" else 0)

        win = window_state_args(sync)
        n_buf = len(win) - 3
        with mesh:
            out = compiled(jax.tree.map(jnp.asarray, div), *win)
        wa = jax.tree.map(lambda x: np.asarray(x), out[3 + n_buf])

        rec[tok] = {
            "ring_bytes": ring_bytes + scale_bytes,
            "scale_bytes": scale_bytes,
            "outer_payload_bytes": pod_bytes,
            "outer_collectives": len(audit["outer"]),
        }
        if tok == "f32":
            rec[tok]["wa_rel_ulp_err"] = 0.0     # oracle leg
            wa_f32 = wa
        else:
            rec[tok]["ring_hbm_ratio"] = (rec["f32"]["ring_bytes"]
                                          / rec[tok]["ring_bytes"])
            rec[tok]["outer_payload_ratio"] = (
                rec["f32"]["outer_payload_bytes"] / pod_bytes
                if pod_bytes else 0.0)
            rec[tok]["wa_rel_ulp_err"] = max(
                rel_ulp_error(r, g, tok)
                for r, g in zip(jax.tree.leaves(wa_f32),
                                jax.tree.leaves(wa)))
    return rec


def _worker():
    print(json.dumps(comms_record()))


def main(print_fn=print):
    from benchmarks.common import run_forced_device_worker
    rec = run_forced_device_worker(__file__, _WORKER_FLAG,
                                   error_row="sync/comms/ERROR",
                                   print_fn=print_fn)
    for tok in TOKENS:
        r = rec[tok]
        print_fn(csv_row(
            f"sync/comms/{tok}", 0.0,
            f"ring_bytes={r['ring_bytes']:.3e};"
            f"outer_payload_bytes={r['outer_payload_bytes']:.3e};"
            f"ring_hbm_ratio={r.get('ring_hbm_ratio', 1.0):.2f};"
            f"outer_payload_ratio={r.get('outer_payload_ratio', 1.0):.2f};"
            f"wa_rel_ulp_err={r['wa_rel_ulp_err']:.3f}"))
    return rec


if __name__ == "__main__":
    if _WORKER_FLAG in sys.argv:
        _worker()
    else:
        main()
