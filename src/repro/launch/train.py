"""Training launcher: HWA (and baselines) on any assigned architecture.

Entry point for the full stack: config registry → synthetic data → HWA
trainer → checkpoints. ``--preset smoke`` (default) is the CPU-scale
variant; ``--preset full --n-layers N`` is the published config at its
published widths with only the depth cut, which is what runs on a TPU
(flash attention and the fused WA sync as compiled Pallas kernels):

  PYTHONPATH=src python -m repro.launch.train --arch granite-3-2b \
      --method hwa --steps 300 --k 2 --window 10
  python -m repro.launch.train --preset full --n-layers 1 --steps 8 \
      --sync-period 4 --window 2 --batch-size 4 --seq-len 1024

``--mesh-native`` instead runs the shard_map SPMD path: K replicas on the
``replica`` mesh axis, one weight pmean per sync (no devices? force host
devices first):

  XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
      python -m repro.launch.train --mesh-native --steps 16 --sync-period 4

Add ``--sync-tree two-level --k 4 --outer-every 2`` for the hierarchical
sync tree: K replicas carved into pods, pod-internal averaging every H
steps, the cross-pod all-reduce + window push only every H·H₂ steps.
``--wa-dtype bf16`` (or ``fp8``) compresses the WA ring storage and
``--comms-dtype`` the tree's cross-pod payload — both routed through
``SyncPlan``; the f32 defaults stay bit-identical to the uncompressed
path.
"""
from __future__ import annotations

import argparse
import functools
import json
import os

from repro.common.compile_cache import use_compile_cache
from repro.configs import ARCH_IDS, PRESETS, get_preset
from repro.core.hwa import HWAConfig
from repro.data import DataPipeline, make_markov_lm_dataset
from repro.models.registry import build_model
from repro.train.trainer import (SPAN_BATCH, SPAN_CALLBACK, SPAN_CHECKPOINT,
                                 SPAN_INNER_STEP, SPAN_LOSS_READ, SPAN_STEP,
                                 SPAN_SYNC, TrainConfig, Trainer, lm_task)


def add_preset_args(ap: argparse.ArgumentParser) -> None:
    """``--preset``/``--n-layers``, shared with ``repro.launch.serve``."""
    ap.add_argument("--preset", default="smoke", choices=PRESETS,
                    help="smoke: CPU-scale variant; full: the published "
                         "config at its published widths")
    ap.add_argument("--n-layers", type=int, default=0,
                    help="--preset full only: cut the depth to N layers "
                         "(0 = the published depth)")


def launcher_config(args):
    """The model config a launcher runs: ``--preset``/``--n-layers``,
    then ``--attn-impl`` (``--preset full`` defaults to flash_pallas).
    Prints the config and the keys cut from the published one."""
    if args.n_layers and args.preset != "full":
        raise SystemExit("--n-layers cuts the published config; add "
                         "--preset full")
    cfg, reduced = get_preset(args.arch, args.preset, args.n_layers)
    attn = args.attn_impl or ("flash_pallas" if args.preset == "full"
                              else "")
    if attn:
        cfg = cfg.with_(attn_impl=attn)
    cut = "smoke widths" if reduced is None else f"reduced={reduced}"
    print(f"[config] {cfg.name} ({args.preset}): layers={cfg.n_layers} "
          f"d_model={cfg.d_model} heads={cfg.n_heads}/{cfg.n_kv_heads} "
          f"d_ff={cfg.d_ff} vocab={cfg.vocab_size} attn={cfg.attn_impl} "
          f"dtype={cfg.dtype} {cut}")
    return cfg


def mesh_batch(step, shape, vocab: int) -> dict:
    """The mesh loop's batch for ``step``: uniform tokens and targets of
    ``shape`` (K, B, S) from ``key(1000 + step)``, a stateless function of
    the step index (so a resume repeats it)."""
    import jax
    ks = jax.random.split(jax.random.key(1000 + step), 2)
    return {"tokens": jax.random.randint(ks[0], shape, 0, vocab),
            "targets": jax.random.randint(ks[1], shape, 0, vocab)}


def run_mesh_native(args, on_step=None, init=None) -> dict:
    """Train with the shard_map HWA steps on a (replica=K, data,
    model=--tp) mesh built from whatever devices are available — or, with
    ``--sync-tree two-level``, on a pod-carved (pod, replica, data,
    model=--tp) mesh where only every ``--outer-every``-th sync crosses
    pods (the rest are pod-internal restarts with zero cross-pod bytes).
    ``--fsdp --tp 2`` exercises the FSDP mixed data×model tilings whose
    sync runs through the GROUPED mesh-resident packed layout (per-group
    window buffers; no legacy GSPMD assembly).

    Inter-replica traffic happens only inside the sync steps — the
    paper's H-fold communication amortization (×H₂ more for cross-pod
    links under the tree), executed for real (one process, SPMD across
    the local devices).

    The host reads each step's losses one step behind: step n's once step
    n+1, or the sync after step n, is queued behind them. Only reads that
    must come before the next dispatch wait on the chips: ``--resilient``'s
    ``alive`` (it decides the next step's optimizer state), a checkpoint's
    ``meta`` and the last step's losses; the result's ``stalled_reads``
    counts them. The outer-sync count is kept on the host and checked
    against the sync program's ``cycle`` at those reads and at the end.

    ``on_step(step, live)``, when given, is called once per step, after
    the step is dispatched (and step − 1's losses are read) and before
    that step's sync (if any), like the Trainer's ``on_step``. ``live``
    holds the loop's arrays as they stand:
    ``inner``, ``inner_opt``, ``losses`` (K,), ``batch``, ``window`` (the
    sync's window arguments: ring, [scales], total, [comp], count,
    next_idx, cycle), ``wa``, and the ``pack_spec`` of the ring and the
    compiled ``programs`` (``train``, ``sync``). It runs synchronously;
    an exception it raises ends the loop. ``init(key)``, when given,
    makes the initial weights from ``--seed``'s key in ``lm.init``'s
    place (the Trainer's ``Task.init``).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.profiler import StepTraceAnnotation, TraceAnnotation

    from repro.common.compat import make_mesh
    from repro.common.quant import is_compressed, needs_scales
    from repro.launch.specs import input_specs
    from repro.launch.steps import (SyncPlan, TwoLevel, build_hwa_bundles,
                                    window_state_args)
    from repro.models.types import InputShape
    from repro.sharding.rules import make_tp_rules

    n_dev = len(jax.devices())
    K = args.k
    tp = max(args.tp, 1)
    if n_dev % (K * tp) or n_dev // (K * tp) < 1:
        raise SystemExit(
            f"--mesh-native needs a device count divisible by K×tp="
            f"{K * tp} (have {n_dev}; set XLA_FLAGS="
            "--xla_force_host_platform_device_count=<n>)")
    tree = args.sync_tree == "two-level"
    if tree:
        pods = args.pods or 2
        if K % pods or K // pods < 1:
            raise SystemExit(f"--sync-tree two-level needs K divisible by "
                             f"--pods (K={K}, pods={pods})")
        mesh = make_mesh((pods, K // pods, n_dev // (K * tp), tp),
                         ("pod", "replica", "data", "model"))
        replica_axis = ("pod", "replica")
        topo = TwoLevel("replica", "pod", outer_every=args.outer_every)
    else:
        mesh = make_mesh((K, n_dev // (K * tp), tp),
                         ("replica", "data", "model"))
        replica_axis = "replica"
        topo = None
    rules = make_tp_rules(mesh, replica_axis=replica_axis, fsdp=args.fsdp)
    cfg = launcher_config(args)
    if cfg.attn_impl == "flash_pallas" and tp > 1:
        raise SystemExit("--attn-impl flash_pallas runs the fully-manual "
                         "DP-only train step; --tp must stay 1")
    if cfg.family in ("vlm", "audio"):
        raise SystemExit(f"{args.arch}: mesh-native driver supports LM "
                         "families only")
    lm = build_model(cfg)
    hwa_cfg = HWAConfig(n_replicas=K, window=args.window,
                        outer_every=args.outer_every if tree else 1,
                        resilient=args.resilient,
                        max_param_rms=args.max_param_rms or None)
    shape = InputShape("mesh_native", seq_len=args.seq_len,
                       global_batch=args.batch_size, kind="train")
    specs, dims = input_specs(cfg, shape)
    try:
        plan = SyncPlan(hwa=hwa_cfg, topology=topo,
                        wa_dtype=args.wa_dtype, comms_dtype=args.comms_dtype,
                        optimizer="sgd", lr=args.lr)
    except ValueError as e:
        raise SystemExit(f"invalid --wa-dtype/--comms-dtype combination: "
                         f"{e}") from None
    bundles = build_hwa_bundles(lm, rules, plan, specs, dims)
    train, sync = bundles.train, bundles.sync
    inner_sync = bundles.inner_sync
    H = args.sync_period or 8

    params = (init or lm.init)(jax.random.key(args.seed))
    inner = jax.tree.map(lambda x: jnp.broadcast_to(x[None], (K,) + x.shape),
                         params)
    from repro.launch.steps import _mk_optimizer
    opt = _mk_optimizer("sgd")   # must match the compiled step's optimizer
    inner_opt = jax.vmap(opt.init)(inner)
    spec = bundles.pack_spec    # window state is packed: one (I, P) ring
    # (or, under FSDP's grouped mixed-tiling layout, one ring per group).
    # The sync bundle's own argument order — (ring, [scales], total,
    # [comp], count, next_idx, cycle) — is the one source of truth for
    # what the window state holds; allocate straight from it.
    win = list(window_state_args(bundles))
    n_buf = len(win) - 3        # buffers ahead of count/next_idx/cycle
    has_scales = needs_scales(spec.ring_dtype)
    has_comp = is_compressed(spec.ring_dtype)
    cycle = win[-1]

    inject = None
    if args.inject_nan:
        s, _, r = args.inject_nan.partition(":")
        inject = (int(s), int(r))
        if not 0 <= inject[1] < K:
            raise SystemExit(f"--inject-nan replica {inject[1]} out of "
                             f"range [0, {K})")

    session = None
    if args.checkpoint_dir and args.checkpoint_every > 0:
        from repro.resilience.session import CheckpointSession
        session = CheckpointSession(args.checkpoint_dir, keep=args.keep)
    if session is None and args.resume:
        raise SystemExit("--resume needs --checkpoint-dir and "
                         "--checkpoint-every")

    def _window_like(win):
        from repro.core.offline import WindowState
        it = iter(win)
        ring = next(it)
        scales = next(it) if has_scales else None
        total = next(it)
        comp = next(it) if has_comp else None
        count, nidx = next(it), next(it)
        return WindowState(ring=ring, total=total, count=count,
                           next_idx=nidx, window=args.window, kind="ring",
                           spec=spec, comp=comp, scales=scales)

    train_c = train.lower(mesh).compile()
    sync_c = sync.lower(mesh).compile()
    inner_sync_c = inner_sync.lower(mesh).compile() if inner_sync else None
    wa = params
    loss = float("nan")
    history = []
    sync_idx = 0
    cycles = 0              # outer syncs done: the sync program's ``cycle``
    start_step = 0
    k_alive_min = K
    if session is not None and args.resume:
        latest = session.latest_intact()
        if latest is not None:
            # everything else about the run — batches, schedules — is a
            # stateless function of (seed, step): restoring the arrays
            # and the step counter IS a bit-exact resume
            inner = jax.device_put(session.load(latest, "inner", inner),
                                   train.in_shardings[0])
            inner_opt = jax.device_put(
                session.load(latest, "inner_opt", inner_opt),
                train.in_shardings[1])
            wa = jax.device_put(session.load(latest, "wa", wa),
                                sync.out_shardings[3 + n_buf])
            ws = session.load_window(latest, _window_like(win))
            restored = [ws.ring]
            if has_scales:
                restored.append(ws.scales)
            restored.append(ws.total)
            if has_comp:
                restored.append(ws.comp)
            for i, buf in enumerate(restored):
                win[i] = jax.device_put(buf, sync.in_shardings[1 + i])
            win[n_buf], win[n_buf + 1] = ws.count, ws.next_idx
            meta = session.meta(latest)
            start_step = int(meta["step"])
            cycles = int(meta["cycle"])
            cycle = win[-1] = jnp.asarray(cycles, jnp.int32)
            sync_idx = int(meta["sync_idx"])
            loss = float(meta["loss"])
            history = list(meta.get("history", []))
            print(f"[mesh-native] resumed from step {start_step} "
                  f"({session.step_dir(latest)})")
    tokens = K * args.batch_size * args.seq_len
    # each chip makes its own replica's rows, in the train program's batch
    # sharding; the step is traced, so one program serves every step
    make_batch = jax.jit(
        functools.partial(mesh_batch, shape=(K, args.batch_size,
                                             args.seq_len),
                          vocab=cfg.vocab_size),
        out_shardings=train.in_shardings[2])
    pending = None          # (step, losses): dispatched, not yet read
    stalled_reads = 0

    def host_read(x, queued: bool):
        """``x`` on the host; ``queued`` says whether a program is queued
        behind it, so that the chips do not wait on this read."""
        nonlocal stalled_reads
        stalled_reads += not queued
        return jax.device_get(x)

    def read_loss(step: int, queued: bool) -> None:
        # reduce on host: jnp.mean over the replica-sharded losses would
        # launch a tiny all-reduce executable whose straggler groups keep
        # holding collective threads after float() reads device 0's shard
        # — the next dispatched step then deadlocks the CPU rendezvous
        # pool. device_get drains every shard.
        nonlocal loss, pending
        of_step, losses = pending
        with TraceAnnotation(SPAN_LOSS_READ, step=step, of_step=of_step):
            loss = float(np.mean(host_read(losses, queued)))
        pending = None

    def check_cycles(device_cycle) -> None:
        if int(device_cycle) != cycles:
            raise RuntimeError(f"the sync program counts {int(device_cycle)}"
                               f" outer syncs, the loop {cycles}")

    with mesh:
        for step in range(start_step, args.steps):
            with StepTraceAnnotation(SPAN_STEP, step_num=step):
                if inject is not None and step == inject[0]:
                    from repro.resilience.faults import poison_replica
                    inner = jax.device_put(poison_replica(inner, inject[1]),
                                           train.in_shardings[0])
                    print(f"[mesh-native] step {step}: injected NaN into "
                          f"replica {inject[1]}")
                with TraceAnnotation(SPAN_BATCH, step=step):
                    batch = make_batch(step)
                with TraceAnnotation(SPAN_INNER_STEP, step=step,
                                     tokens=tokens):
                    inner, inner_opt, losses = train_c(inner, inner_opt,
                                                       batch)
                if pending is not None:      # step - 1's, this one queued
                    read_loss(step, queued=True)
                pending = (step, losses)
                if on_step is not None:
                    with TraceAnnotation(SPAN_CALLBACK, step=step):
                        on_step(step, {
                            "inner": inner, "inner_opt": inner_opt,
                            "losses": losses, "batch": batch,
                            "window": win, "wa": wa, "pack_spec": spec,
                            "programs": {"train": train_c,
                                         "sync": sync_c}})
                if (step + 1) % H == 0:
                    if inner_sync_c is not None and \
                            not topo.is_outer(sync_idx):
                        # pod-internal restart: zero cross-pod traffic, no
                        # window push (the window collects global W̄ only)
                        with TraceAnnotation(SPAN_SYNC, cycle=sync_idx + 1):
                            inner = inner_sync_c(inner)
                        read_loss(step, queued=True)
                        history.append({"step": step + 1, "loss": loss,
                                        "sync": "inner"})
                        print(f"[mesh-native] step {step + 1} loss {loss:.4f} "
                              f"inner sync (pods avg internally)")
                    else:
                        # outputs mirror the inputs: (inner, <buffers...>,
                        # count, next_idx, wa, cycle[, alive])
                        with TraceAnnotation(SPAN_SYNC, cycle=sync_idx + 1):
                            res = sync_c(inner, *win)
                        inner = res[0]
                        count, nidx, wa, cycle = res[1 + n_buf:5 + n_buf]
                        win = list(res[1:1 + n_buf]) + [count, nidx, cycle]
                        read_loss(step, queued=True)
                        cycles += 1
                        if args.resilient:
                            alive = res[5 + n_buf]
                            alive_h, cycle_h = host_read((alive, cycle),
                                                         queued=False)
                            check_cycles(cycle_h)
                            k_alive = int(np.sum(alive_h))
                            k_alive_min = min(k_alive_min, k_alive)
                            if k_alive < K:
                                # the sync already restarted the dead replica
                                # from W̄; its stale momentum goes too
                                from repro.resilience.health import \
                                    quarantine_opt_state
                                inner_opt = jax.device_put(
                                    quarantine_opt_state(inner_opt, alive),
                                    train.in_shardings[1])
                            history.append({"step": step + 1, "loss": loss,
                                            "sync": "outer",
                                            "cycle": cycles,
                                            "k_alive": k_alive})
                            print(f"[mesh-native] step {step + 1} loss "
                                  f"{loss:.4f} cycle {cycles} "
                                  f"k_alive {k_alive}/{K}")
                        else:
                            history.append({"step": step + 1, "loss": loss,
                                            "sync": "outer",
                                            "cycle": cycles})
                            print(f"[mesh-native] step {step + 1} loss "
                                  f"{loss:.4f} cycle {cycles} (K={K}, "
                                  f"mesh={dict(mesh.shape)})")
                    sync_idx += 1
                save = session is not None and \
                    (step + 1) % args.checkpoint_every == 0
                if pending is not None and (save or step == args.steps - 1):
                    read_loss(step, queued=False)
                if save:
                    with TraceAnnotation(SPAN_CHECKPOINT, step=step):
                        check_cycles(host_read(cycle, queued=False))
                        session.save(
                            step + 1,
                            {"inner": inner, "inner_opt": inner_opt,
                             "wa": wa},
                            window=_window_like(win),
                            meta={"step": step + 1, "cycle": cycles,
                                  "sync_idx": sync_idx, "loss": loss,
                                  "history": history})
    check_cycles(jax.device_get(cycle))
    wa_finite = all(bool(np.all(np.isfinite(jax.device_get(x))))
                    for x in jax.tree.leaves(wa)
                    if jnp.issubdtype(x.dtype, jnp.floating))
    ws_final = _window_like(win)
    out = {"final_loss": loss, "cycles": cycles, "syncs": sync_idx,
           "history": history, "sync_tree": args.sync_tree,
           "wa_dtype": plan.wa_dtype, "comms_dtype": plan.comms_dtype,
           "wa_finite": wa_finite, "k_alive_min": k_alive_min,
           "stalled_reads": stalled_reads,
           "mesh": {k: int(v) for k, v in mesh.shape.items()},
           "_state": {"inner": inner, "wa": wa, "ring": ws_final.ring,
                      "total": ws_final.total}}
    print(f"[mesh-native] done: {out['cycles']} outer cycles / "
          f"{sync_idx} syncs, final loss {out['final_loss']:.4f}, "
          f"wa_finite {wa_finite}")
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-2b", choices=ARCH_IDS)
    add_preset_args(ap)
    ap.add_argument("--method", default="hwa",
                    choices=["base", "ca", "swa", "ema", "lookahead", "sam",
                             "online", "pmsgd", "hwa"])
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch-size", type=int, default=16)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--lr", type=float, default=0.3)
    ap.add_argument("--k", type=int, default=2, help="HWA replicas K")
    ap.add_argument("--sync-period", type=int, default=0, help="H (0=epoch)")
    ap.add_argument("--window", type=int, default=10, help="I")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--attn-impl", default="",
                    choices=["", "naive", "flash_jnp", "flash_pallas"],
                    help="override the arch's attention implementation "
                         "(--preset full defaults to flash_pallas); "
                         "flash_pallas selects the Pallas custom-vjp "
                         "kernels (fully-manual DP train step under "
                         "--mesh-native; interpret mode off-TPU)")
    ap.add_argument("--out", default="")
    ap.add_argument("--mesh-native", action="store_true",
                    help="run the shard_map SPMD HWA path on the local "
                         "devices (replica axis = K)")
    ap.add_argument("--sync-tree", default="flat",
                    choices=["flat", "two-level"],
                    help="sync topology (mesh-native only): flat = one "
                         "global all-reduce per sync; two-level = pods "
                         "average internally every sync, cross-pod "
                         "all-reduce + window push every --outer-every "
                         "syncs")
    ap.add_argument("--outer-every", type=int, default=2,
                    help="H₂: outer (cross-pod) sync period of the "
                         "two-level tree, in syncs")
    ap.add_argument("--pods", type=int, default=0,
                    help="pod count for --sync-tree two-level "
                         "(0 = auto: 2)")
    ap.add_argument("--wa-dtype", default="f32",
                    choices=["f32", "bf16", "fp8"],
                    help="mesh-native only: WA ring storage dtype — bf16 "
                         "halves the window's HBM, fp8 (block-scaled, "
                         "per-ALIGN-block f32 scales) quarters it; the "
                         "running total stays f32 with Kahan "
                         "compensation. f32 (default) is bit-identical "
                         "to the uncompressed path")
    ap.add_argument("--comms-dtype", default="f32",
                    choices=["f32", "bf16", "fp8"],
                    help="mesh-native only: cross-pod sync payload dtype "
                         "(needs --sync-tree two-level; incompatible "
                         "with --resilient)")
    ap.add_argument("--fsdp", action="store_true",
                    help="mesh-native only: FSDP rule table (params + "
                         "moments sharded over the data axes too) — the "
                         "mixed data/model tilings the GROUPED "
                         "mesh-resident packed sync covers")
    ap.add_argument("--tp", type=int, default=1,
                    help="mesh-native only: model (tensor-parallel) axis "
                         "size; with --fsdp this yields true mixed "
                         "data×model leaf tilings")
    ap.add_argument("--resilient", action="store_true",
                    help="alive-masked sync: a replica whose weights go "
                         "non-finite (or whose RMS exceeds "
                         "--max-param-rms) is excluded from the K-mean "
                         "and re-seeded from W̄ at the next sync")
    ap.add_argument("--max-param-rms", type=float, default=0.0,
                    help="resilient only: divergence threshold on a "
                         "replica's parameter RMS (0 = finiteness only)")
    ap.add_argument("--inject-nan", default="",
                    help="fault injection (mesh-native only): STEP:REPLICA "
                         "— poison that replica's weights with NaN before "
                         "that step")
    ap.add_argument("--checkpoint-dir", default="",
                    help="preemption-safe checkpoint session directory "
                         "(manifest-last + CRC-verified)")
    ap.add_argument("--checkpoint-every", type=int, default=0,
                    help="steps between checkpoints (0 = off)")
    ap.add_argument("--keep", type=int, default=3,
                    help="checkpoints retained (older ones are GC'd)")
    ap.add_argument("--resume", action="store_true",
                    help="resume from the newest INTACT checkpoint in "
                         "--checkpoint-dir (bit-exact: torn/corrupted "
                         "saves are skipped)")
    args = ap.parse_args()
    use_compile_cache()

    if args.inject_nan and not args.mesh_native:
        raise SystemExit("--inject-nan needs --mesh-native (use "
                         "tools/fault_check.py for the in-process legs)")
    if (args.wa_dtype != "f32" or args.comms_dtype != "f32") \
            and not args.mesh_native:
        raise SystemExit("--wa-dtype/--comms-dtype compress the "
                         "mesh-native packed window state; add "
                         "--mesh-native")

    if args.mesh_native:
        out = run_mesh_native(args)
        if args.out:
            os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                        exist_ok=True)
            with open(args.out, "w") as f:
                # "_"-prefixed keys carry device arrays for in-process
                # callers (fault harness) — not JSON material
                json.dump({k: v for k, v in out.items()
                           if not k.startswith("_")}, f, indent=2)
        return

    cfg = launcher_config(args)
    if cfg.family in ("vlm", "audio"):
        raise SystemExit(f"{args.arch}: use examples/serve_decode.py-style "
                         "drivers for modality-frontend archs")
    lm = build_model(cfg)
    ds = make_markov_lm_dataset(vocab=cfg.vocab_size, seq_len=args.seq_len,
                                n_train=2048, n_test=512, seed=args.seed)
    K = args.k if args.method in ("hwa", "online", "pmsgd") else 1
    pipe = DataPipeline(ds, batch_size=args.batch_size, n_replicas=K,
                        seed=args.seed)
    tc = TrainConfig(
        method=args.method, total_steps=args.steps,
        batch_size=args.batch_size, base_lr=args.lr, seed=args.seed,
        hwa=HWAConfig(n_replicas=K, sync_period=args.sync_period,
                      window=args.window, resilient=args.resilient,
                      max_param_rms=args.max_param_rms or None,
                      use_kernels=True),
        checkpoint_dir=args.checkpoint_dir,
        checkpoint_every=args.checkpoint_every,
        checkpoint_keep=args.keep, resume=args.resume)
    out = Trainer(lm_task(lm, pipe), tc).run(log=True)
    print(f"[train] {args.arch}/{args.method}: final {out['final']}, "
          f"best {out['best']}")
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"final": out["final"], "best": out["best"],
                       "history": out["history"]}, f, indent=2)


if __name__ == "__main__":
    main()
