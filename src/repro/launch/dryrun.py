import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The dry-run only lowers and INTROSPECTS compiled artifacts — no tensor
# is ever materialized, so the CPU miscompile of the legacy GSPMD
# packed-W̄ assembly (launch/sync/legacy.py) cannot corrupt
# anything here. Allow the FSDP hwa_sync combos to keep compiling on the
# forced-host meshes instead of tripping the hard error.
os.environ.setdefault("REPRO_ALLOW_LEGACY_ASSEMBLY", "1")

"""Multi-pod dry-run: lower + compile every (arch × input-shape × mesh)
combination against the production meshes and extract the roofline terms.

No tensor is ever allocated — inputs are ShapeDtypeStructs, and the
compiled artifact is only introspected (memory_analysis / cost_analysis /
post-SPMD HLO). A failure here (sharding mismatch, OOM at compile,
unsupported collective) is a bug in the framework.

Usage:
  python -m repro.launch.dryrun --arch all --shape all --mesh both \
      --out experiments/dryrun
  python -m repro.launch.dryrun --arch gemma2-27b --shape train_4k \
      --mesh single --step hwa_train        # HWA-stacked variant
"""

import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.compat import tree_flatten_with_path
from repro.configs import ARCH_IDS, get_config, get_input_shape
from repro.core.hwa import HWAConfig
from repro.launch.hlo import roofline_terms
from repro.launch.hlo_cost import analyze_hlo
from repro.launch.mesh import make_hwa_mesh, make_production_mesh
from repro.launch.specs import (adapt_config_for_shape, cache_specs,
                                decode_token_specs, input_specs)
from repro.launch.steps import (make_decode_step, make_hwa_sync_step,
                                make_hwa_train_step, make_prefill_step,
                                make_train_step)
from repro.models.registry import build_model
from repro.models.types import INPUT_SHAPES
from repro.sharding.rules import ShardingRules, make_tp_rules

HBM_PER_CHIP = 16e9   # v5e


def count_params(params_abs, cfg):
    total = embed = moe_routed = 0
    for path, leaf in tree_flatten_with_path(params_abs)[0]:
        n = int(np.prod(leaf.shape))
        keys = "/".join(str(getattr(p, "key", p)) for p in path)
        total += n
        if keys.startswith("embed"):
            embed += n
        if "moe" in keys and any(w in keys for w in ("w_gate", "w_up",
                                                     "w_down")):
            moe_routed += n
    active = total - moe_routed
    if cfg.n_experts:
        active += moe_routed * cfg.top_k / cfg.n_experts
    return {"total": total, "embed": embed,
            "active": active, "active_nonembed": active - embed,
            "nonembed": total - embed}


def model_flops(cfg, shape, pcount):
    n = pcount["active_nonembed"]
    if shape.kind == "train":
        return 6.0 * n * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n * shape.global_batch * shape.seq_len
    return 2.0 * n * shape.global_batch          # decode: one token/seq


def _sharded_bytes(abs_tree, dims_tree, rules):
    """Per-device bytes of a spec'd pytree under the given rules."""
    import math
    from repro.sharding.rules import spec_for_dims
    is_dims = lambda t: isinstance(t, tuple) and all(
        isinstance(e, (str, type(None))) for e in t)
    total = 0
    leaves_a = jax.tree.leaves(abs_tree)
    leaves_d = jax.tree.leaves(dims_tree, is_leaf=is_dims)
    for leaf, d in zip(leaves_a, leaves_d):
        spec = spec_for_dims(rules.mesh, rules.rules, d, leaf.shape)
        shard = 1
        for dim_size, assignment in zip(leaf.shape,
                                        tuple(spec) + (None,) * len(leaf.shape)):
            if assignment is None:
                continue
            axes = assignment if isinstance(assignment, tuple) else (assignment,)
            shard *= math.prod(rules.mesh.shape[a] for a in axes)
        total += int(np.prod(leaf.shape)) * leaf.dtype.itemsize // shard
    return total


def build_bundle(arch, shape_name, step_kind, mesh, hwa_k=2, variant=""):
    shape = get_input_shape(shape_name)
    cfg = adapt_config_for_shape(get_config(arch), shape)
    if variant == "ep":
        cfg = cfg.with_(expert_parallel=True)
    elif variant == "cf1":
        cfg = cfg.with_(moe_capacity_factor=1.0)
    lm = build_model(cfg)
    replica_axis = "replica" if "replica" in mesh.shape else None
    train_like = step_kind == "train" or step_kind.startswith("hwa_")
    # Training/prefill: full FSDP (params + moments) + sequence
    # parallelism. Decode: TP-only weights (latency path, no opt state).
    fsdp_like = train_like or step_kind == "prefill"
    rules = make_tp_rules(mesh, replica_axis=replica_axis,
                          fsdp=fsdp_like, sequence_parallel=train_like,
                          expert_parallel=cfg.expert_parallel)
    opt_rules = rules
    if step_kind == "decode":
        data_sz = 1
        for a in ("pod", "data"):
            if a in mesh.shape:
                data_sz *= mesh.shape[a]
        if shape.global_batch % data_sz:
            # batch-1 long-context decode: context-parallel KV cache
            # (cache seq dim sharded over the idle data axes)
            rules = ShardingRules(mesh=rules.mesh,
                                  rules={**rules.rules,
                                         "seq": tuple(
                                             a for a in ("pod", "data")
                                             if a in mesh.shape)})

    if step_kind == "train":
        specs, dims = input_specs(cfg, shape)
        n_params = sum(int(np.prod(l.shape))
                       for l in jax.tree.leaves(lm.abstract()[0]))
        n_mb = 4 if n_params > 2e10 else (2 if n_params > 8e9 else 1)
        bundle = make_train_step(lm, rules, specs, dims,
                                 opt_rules=opt_rules, n_microbatches=n_mb)
    elif step_kind == "prefill":
        specs, dims = input_specs(cfg, shape)
        c_abs, c_dims = cache_specs(lm, shape)
        bundle = make_prefill_step(lm, rules, specs, dims, c_abs, c_dims)
        bundle.cache_bytes_per_dev = _sharded_bytes(c_abs, c_dims, rules)
    elif step_kind == "decode":
        t_abs, t_dims = decode_token_specs(cfg, shape)
        c_abs, c_dims = cache_specs(lm, shape)
        bundle = make_decode_step(lm, rules, t_abs, t_dims, c_abs, c_dims)
        bundle.cache_bytes_per_dev = _sharded_bytes(c_abs, c_dims, rules)
    elif step_kind == "hwa_train":
        import dataclasses as dc
        per_replica = dc.replace(shape, global_batch=shape.global_batch // hwa_k)
        specs, dims = input_specs(cfg, per_replica)
        n_params = sum(int(np.prod(l.shape))
                       for l in jax.tree.leaves(lm.abstract()[0]))
        n_mb = 4 if n_params > 2e10 else (2 if n_params > 8e9 else 1)
        if cfg.n_experts:
            n_mb = max(n_mb, 2)
        bundle = make_hwa_train_step(lm, rules, specs, dims,
                                     HWAConfig(n_replicas=hwa_k),
                                     opt_rules=opt_rules,
                                     n_microbatches=n_mb)
    elif step_kind == "hwa_sync":
        bundle = make_hwa_sync_step(lm, rules, HWAConfig(n_replicas=hwa_k))
    elif step_kind == "hwa_sync_bf16ring":
        bundle = make_hwa_sync_step(lm, rules, HWAConfig(n_replicas=hwa_k),
                                    ring_dtype=jnp.bfloat16)
    elif step_kind == "hwa_sync_streaming":
        bundle = make_hwa_sync_step(
            lm, rules,
            HWAConfig(n_replicas=hwa_k, window_kind="streaming"))
    else:
        raise ValueError(step_kind)
    return cfg, lm, bundle


def run_combo(arch, shape_name, mesh_kind, step_kind="auto", hwa_k=2,
              verbose=True, variant=""):
    shape = get_input_shape(shape_name)
    if step_kind == "auto":
        step_kind = {"train": "train", "prefill": "prefill",
                     "decode": "decode"}[shape.kind]
    if step_kind.startswith("hwa_"):
        mesh = make_hwa_mesh(hwa_k, multi_pod=(mesh_kind == "multi"))
    else:
        mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_dev = int(np.prod(list(mesh.shape.values())))

    cfg, lm, bundle = build_bundle(arch, shape_name, step_kind, mesh, hwa_k,
                                   variant)
    t0 = time.time()
    lowered = bundle.lower(mesh)
    t1 = time.time()
    compiled = lowered.compile()
    t2 = time.time()

    ca = compiled.cost_analysis() or {}
    ma = compiled.memory_analysis()
    # loop-aware structural analysis (XLA cost_analysis counts while
    # bodies once — verified; analyze_hlo multiplies trip counts)
    hc = analyze_hlo(compiled.as_text())
    flops_dev = hc.flops
    bytes_dev = hc.bytes
    terms = roofline_terms(flops_dev, bytes_dev, hc.coll_traffic)
    pcount = count_params(lm.abstract()[0], cfg)
    mflops = model_flops(cfg, shape, pcount)
    peak_dev = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
                + ma.output_size_in_bytes - ma.alias_size_in_bytes)
    # CPU-backend artifact: matmuls lower as f32, so the WHOLE stacked KV
    # cache gets a hoisted f32 convert (2 copies, k+v) that the TPU bf16
    # MXU path would not materialize. Projected TPU peak removes them.
    cache_bytes = getattr(bundle, "cache_bytes_per_dev", 0)
    tpu_peak = peak_dev - 2 * cache_bytes if cache_bytes else peak_dev

    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "step": step_kind, "variant": variant, "n_devices": n_dev,
        "mesh_shape": dict(mesh.shape),
        "flops_per_device": flops_dev,
        "bytes_per_device": bytes_dev,
        "collectives": {"counts": {k: float(v) for k, v in
                                   hc.coll_counts.items()},
                        "result_bytes_by_op": {k: float(v) for k, v in
                                               hc.coll_bytes.items()},
                        "traffic_bytes_per_device": hc.coll_traffic},
        "xla_cost_analysis_raw": {"flops_body_once": float(ca.get("flops", 0.0)),
                                  "bytes_body_once": float(ca.get("bytes accessed", 0.0))},
        "memory": {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "peak_bytes": peak_dev,
            "fits_16GB": bool(peak_dev < HBM_PER_CHIP),
            "cache_bytes_per_dev": cache_bytes,
            "tpu_projected_peak_bytes": tpu_peak,
            "fits_16GB_tpu_projected": bool(tpu_peak < HBM_PER_CHIP),
        },
        "roofline": terms,
        "params": pcount,
        "model_flops_global": mflops,
        "useful_compute_ratio": (mflops / (flops_dev * n_dev)
                                 if flops_dev else 0.0),
        "lower_s": t1 - t0, "compile_s": t2 - t1,
    }
    if verbose:
        print(f"[dryrun] {arch} × {shape_name} × {mesh_kind} ({step_kind}): "
              f"OK — {flops_dev:.3e} FLOP/dev, "
              f"{bytes_dev/1e9:.2f} GB/dev HBM, "
              f"{hc.coll_traffic/1e9:.3f} GB/dev ICI, "
              f"peak {peak_dev/1e9:.2f} GB "
              f"({'fits' if rec['memory']['fits_16GB'] else 'OOM!'}; "
              f"tpu-proj "
              f"{'fits' if rec['memory']['fits_16GB_tpu_projected'] else 'OOM!'}), "
              f"dominant={terms['dominant']} "
              f"compile {t2-t1:.1f}s")
        print(f"  memory_analysis: {ma}")
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi",
                                                       "both"])
    ap.add_argument("--step", default="auto")
    ap.add_argument("--hwa-k", type=int, default=2)
    ap.add_argument("--variant", default="", help="ep | cf1 | ''")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    archs = ARCH_IDS if args.arch == "all" else args.arch.split(",")
    shapes = list(INPUT_SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    os.makedirs(args.out, exist_ok=True)

    failures = []
    for arch in archs:
        for shape_name in shapes:
            for mesh_kind in meshes:
                tag = f"{arch}__{shape_name}__{mesh_kind}"
                if args.step != "auto":
                    tag += f"__{args.step}"
                if args.variant:
                    tag += f"__{args.variant}"
                path = os.path.join(args.out, tag + ".json")
                if args.skip_existing and os.path.exists(path):
                    print(f"[dryrun] skip {tag} (exists)")
                    continue
                try:
                    rec = run_combo(arch, shape_name, mesh_kind, args.step,
                                    args.hwa_k, variant=args.variant)
                    with open(path, "w") as f:
                        json.dump(rec, f, indent=2)
                except Exception as e:  # noqa: BLE001 — report, keep going
                    failures.append((tag, repr(e)))
                    print(f"[dryrun] FAIL {tag}: {e}")
                    traceback.print_exc()
    print(f"[dryrun] done; {len(failures)} failures")
    for tag, err in failures:
        print("  FAIL", tag, err[:200])
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
