"""Smoke run of the HWA main path on a TPU, at granite-3-2b's published
widths (d_model 2048, 32 query / 8 KV heads, d_ff 8192, vocab 49,155)
with the depth cut to ``N_LAYERS`` and random weights from a seed.

  python chip_smoke.py               # one chip: train, then serve W̿
  python chip_smoke.py --four-chips  # a four-chip host: mesh-native HWA

One chip: the ``Trainer`` runs K=2 vmapped replicas with H=4, I=2 for
13 steps (3 syncs), flash attention fwd+bwd and the fused WA sync as
compiled Pallas kernels; the third sync (the first that evicts a window
slot) is checked bit-exactly against ``repro.kernels.ref``. W̿ is then
published through ``repro.serve.publish`` into a ``PagedDecodeEngine``
on the Pallas paged kernel, which answers 4 requests (prompt 128, 16
new tokens); its first and last decode steps' logits are checked
against a float32 full forward pass.

Four chips: mesh-native HWA with K=4 replicas on a (replica=4, data=1,
model=1) mesh, flat sync, checked against a host numpy mean of the
replicas' pre-sync weights, plus the HLO audits (no replica collective
in the train step, one all-reduce per sync).

Earlier lines report what ran; the last line is one JSON object naming
the device. Exits non-zero, printing no result, when JAX finds no TPU,
when run outside a checkout of the repository, or when a phase fails.
"""
from __future__ import annotations

import argparse
import base64
import json
import os
import re
import sys
import time

N_LAYERS = 1            # see CHANGES.md: the sync's peak sets the depth
K, H, I = 2, 4, 2       # replicas, sync period, window
STEPS = 13              # syncs after steps 4, 8 and 12
BATCH, SEQ = 4, 1024    # per replica, per step
LR = 0.05
SEED = 0
PROMPT, NEW, PAGE = 128, 16, 16
N_REQUESTS = 4
K4_STEPS = 8            # four-chip phase: syncs after steps 4 and 8

# bf16 serving vs a float32 reference: the system rounds activations to
# bf16 (unit roundoff u = 2**-8) at about eight points between token and
# logit, so allow 8u of the reference's largest logit. fp8 (u = 2**-4)
# or a wrong cache position fails it by an order of magnitude.
LOGIT_RTOL = 8 * 2.0 ** -8
MEAN_ULPS = 4

# the Pallas kernels each compiled program must contain
TRAIN_KERNELS = ("_flash_kernel", "_dq_kernel", "_dkv_kernel")
SYNC_KERNELS = ("_wa_sync_fused_kernel",)
WINDOW_KERNELS = ("_wa_window_update_kernel",)
DECODE_KERNELS = ("_paged_kernel",)


def log(msg: str) -> None:
    print(msg, flush=True)


def kernels_in(hlo: str, names) -> dict:
    """Count of compiled Pallas kernels (``tpu_custom_call``) per kernel
    function name, read from each call's serialized Mosaic body."""
    bodies = [base64.b64decode(m.group(1)) for m in re.finditer(
        r'custom_call_target="tpu_custom_call".*?"body":"([^"]+)"', hlo)]
    return {n: sum(n.encode() in b for b in bodies) for n in names}


def require_kernels(label: str, hlo: str, names) -> None:
    found = kernels_in(hlo, names)
    log(f"[kernels] {label}: tpu_custom_call {found}")
    missing = [n for n, c in found.items() if not c]
    if missing:
        raise AssertionError(f"{label}: no compiled Pallas kernel {missing}")


def peak_gib(dev) -> float:
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use", 0) / 2 ** 30


def granite():
    from repro.configs import get_preset
    cfg, reduced = get_preset("granite-3-2b", "full", N_LAYERS)
    cfg = cfg.with_(attn_impl="flash_pallas")
    log(f"[config] {cfg.name}: layers={cfg.n_layers} d_model={cfg.d_model} "
        f"heads={cfg.n_heads}/{cfg.n_kv_heads} head_dim="
        f"{cfg.resolved_head_dim} d_ff={cfg.d_ff} vocab={cfg.vocab_size} "
        f"dtype={cfg.dtype} attn={cfg.attn_impl} remat={cfg.remat} "
        f"reduced={reduced} source={cfg.source}")
    return cfg


# ------------------------------------------------------------------ train


def check_sync(pre: dict, ws, wa, spec) -> None:
    """The chip's sync against ``kernels.ref.wa_sync_fused_ref`` applied
    to the same packed inputs, on the host CPU. Target: bit-exact (both
    are f32 elementwise math in the same order)."""
    import jax
    import numpy as np

    from repro.common.packing import unpack
    from repro.kernels import ref as kref

    count, idx = pre["count"], pre["next_idx"]
    full = np.float32(count >= I)
    inv = np.float32(1.0) / np.float32(min(count + 1, I))
    with jax.default_device(jax.devices("cpu")[0]):
        ring2, total2, avg = kref.wa_sync_fused_ref(
            pre["stacked"], pre["ring"], pre["total"], idx, full, inv)
        want_wa = unpack(avg, spec, like=wa)
        got = {"ring": np.asarray(jax.device_get(ws.ring)),
               "total": np.asarray(jax.device_get(ws.total))}
        want = {"ring": np.asarray(ring2), "total": np.asarray(total2)}
        for name in got:
            diff = int(np.sum(got[name] != want[name]))
            log(f"[sync-parity] {name}: {diff} of {got[name].size} f32 "
                f"elements differ from kernels/ref.py (full_flag={full})")
            if diff:
                raise AssertionError(f"sync {name} differs from the ref")
        bad = sum(int(np.sum(np.asarray(jax.device_get(g), np.float32)
                             != np.asarray(w, np.float32)))
                  for g, w in zip(jax.tree.leaves(wa),
                                  jax.tree.leaves(want_wa)))
        log(f"[sync-parity] W̿ leaves: {bad} elements differ")
        if bad:
            raise AssertionError("W̿ differs from kernels/ref.py")


def train_phase(cfg, dev):
    import jax
    import numpy as np

    from repro.common.packing import pack_stacked
    from repro.core.hwa import HWAConfig
    from repro.data import DataPipeline, make_markov_lm_dataset
    from repro.models.registry import build_model
    from repro.train.trainer import TrainConfig, Trainer, lm_task

    t0 = time.perf_counter()
    lm = build_model(cfg)
    ds = make_markov_lm_dataset(vocab=cfg.vocab_size, seq_len=SEQ,
                                n_train=4 * BATCH, n_test=BATCH, seed=SEED)
    pipe = DataPipeline(ds, batch_size=BATCH, n_replicas=K, seed=SEED)
    tc = TrainConfig(method="hwa", total_steps=STEPS, batch_size=BATCH,
                     base_lr=LR, seed=SEED,
                     hwa=HWAConfig(n_replicas=K, sync_period=H, window=I,
                                   use_kernels=True))
    trainer = Trainer(lm_task(lm, pipe), tc)
    log(f"[train] data + trainer set-up {time.perf_counter() - t0:.2f}s; "
        f"K={K} H={H} I={I} steps={STEPS} batch={BATCH}x{SEQ} per replica")

    losses, times, box = [], [], {}
    last = [time.perf_counter()]

    def on_step(step, state, metrics):
        loss = float(jax.block_until_ready(metrics["loss"]))
        now = time.perf_counter()
        times.append(now - last[0])
        losses.append(loss)
        if step == 0:
            require_kernels("train step", trainer.hwa_step.lower(
                state, step).compile().as_text(), TRAIN_KERNELS)
        if step == H - 1:           # about to run the first sync
            require_kernels("sync", trainer.sync_step.lower(
                state).compile().as_text(), SYNC_KERNELS)
            log("[sync] builder: core.hwa.hwa_sync fused single-device "
                "path (one hwa_sync_packed launch per sync)")
        if step == STEPS - 2:       # about to run the third sync
            ws = state.window_state
            box["pre"] = {
                "stacked": np.asarray(jax.device_get(
                    pack_stacked(state.inner, ws.spec))),
                "ring": np.asarray(jax.device_get(ws.ring)),
                "total": np.asarray(jax.device_get(ws.total)),
                "count": int(ws.count), "next_idx": int(ws.next_idx)}
        if step == STEPS - 1:       # the last sync has run
            box["ws"] = state.window_state
            check_sync(box.pop("pre"), state.window_state, state.wa,
                       state.window_state.spec)
        last[0] = time.perf_counter()

    out = trainer.run(on_step=on_step)
    log("[train] losses " + " ".join(f"{x:.4f}" for x in losses))
    log("[train] step seconds (first includes compile) "
        + " ".join(f"{t:.4f}" for t in times))
    if not all(np.isfinite(losses)):
        raise AssertionError(f"non-finite training loss: {losses}")
    log(f"[train] final W̿ eval {out['final']}; peak_bytes_in_use "
        f"{peak_gib(dev):.2f} GiB")
    return lm, out["params"], box["ws"]


# ------------------------------------------------------------------ serve


def serve_phase(lm, wa, ws, dev):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models.registry import build_model
    from repro.serve.engine import PagedDecodeEngine
    from repro.serve.publish import WeightPublisher
    from repro.serve.scheduler import ContinuousScheduler, Request

    class RecordingEngine(PagedDecodeEngine):
        """Keeps every decode step's logits (and the first step's
        compiled program) for the checks below."""

        def step(self, ctrl):
            if not self.step_logits:
                s = self.state
                dev_ctrl = {k: jnp.asarray(v) for k, v in ctrl.items()}
                self.step_hlo = self._jit_step.lower(
                    self.params, s["caches"], s["last"], s["out"], s["key"],
                    dev_ctrl).compile().as_text()
            super().step(ctrl)
            self.step_logits.append(self.last_logits)

    engine = RecordingEngine(
        lm=lm, params=wa, max_batch=N_REQUESTS, max_seq_len=PROMPT + NEW,
        max_new=NEW, page_size=PAGE, prefill_chunk=PROMPT)
    engine.step_logits = []
    WeightPublisher(engine=engine).publish_window_state(ws)
    params = engine.params
    del ws, wa
    log("[serve] published W̿ from the window state into the engine")

    rng = np.random.default_rng(SEED)
    prompts = rng.integers(0, lm.cfg.vocab_size, (N_REQUESTS, PROMPT),
                           dtype=np.int32)
    reqs = [Request(rid=i, tokens=prompts[i], n_new=NEW)
            for i in range(N_REQUESTS)]
    t0 = time.perf_counter()
    outs = ContinuousScheduler(engine).run(reqs, seed=SEED)
    dt = time.perf_counter() - t0
    gen = np.stack([outs[i] for i in range(N_REQUESTS)])
    log(f"[serve] {N_REQUESTS} requests x {NEW} tokens in {dt:.3f}s "
        f"(first call includes compile); decode steps "
        f"{len(engine.step_logits)}; step traces {engine.step_traces}")
    require_kernels("paged decode step", engine.step_hlo, DECODE_KERNELS)

    # float32 reference: the same weights, naive attention, full forward
    ref_lm = build_model(lm.cfg.with_(attn_impl="naive", dtype="float32"))
    p32 = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    toks = jnp.asarray(np.concatenate([prompts, gen[:, :NEW - 1]], axis=1))
    with jax.default_matmul_precision("highest"):
        ref, _ = jax.jit(ref_lm.apply)(p32, {"tokens": toks})
    # engine slot -> request, read back from the slots' output buffers
    out_rows = np.asarray(engine.state["out"][:, :NEW])
    rid_of = [int(np.flatnonzero((gen == row).all(axis=1))[0])
              for row in out_rows]
    for label, i, pos in (("first", 0, PROMPT), ("last", NEW - 2,
                                                 PROMPT + NEW - 2)):
        got = np.asarray(engine.step_logits[i])
        want = np.asarray(ref[rid_of, pos])
        err = float(np.max(np.abs(got - want)))
        scale = float(np.max(np.abs(want)))
        log(f"[serve] {label} decode step logits vs f32 full forward: "
            f"max|diff| {err:.5f}, max|ref| {scale:.5f}, limit "
            f"{LOGIT_RTOL * scale:.5f}")
        if not err <= LOGIT_RTOL * scale:
            raise AssertionError(f"{label} decode logits off the reference")
    log(f"[serve] peak_bytes_in_use {peak_gib(dev):.2f} GiB")


# ------------------------------------------------------------ four chips


def four_chip_phase(cfg, devices):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.common.compat import make_mesh
    from repro.core.hwa import HWAConfig
    from repro.launch.hlo import (collectives_crossing_axis,
                                  sync_collective_audit)
    from repro.launch.specs import input_specs
    from repro.launch.steps import (SyncPlan, build_hwa_bundles,
                                    window_state_args)
    from repro.launch.sync.bundles import _mk_optimizer as plan_optimizer
    from repro.models.registry import build_model
    from repro.models.types import InputShape
    from repro.sharding.rules import make_tp_rules

    K4 = 4
    mesh = make_mesh((K4, 1, 1), ("replica", "data", "model"),
                     devices=devices[:K4])
    rules = make_tp_rules(mesh, replica_axis="replica")
    lm = build_model(cfg)
    specs, dims = input_specs(cfg, InputShape("smoke", seq_len=SEQ,
                                              global_batch=BATCH,
                                              kind="train"))
    plan = SyncPlan(hwa=HWAConfig(n_replicas=K4, window=I,
                                  use_kernels=True),
                    optimizer="sgd", lr=LR)
    bundles = build_hwa_bundles(lm, rules, plan, specs, dims)
    log(f"[four-chips] mesh {dict(mesh.shape)} on "
        f"{[d.id for d in mesh.devices.flat]}; sync builder: "
        f"{bundles.sync.contract.notes}")
    if "mesh-resident" not in (bundles.sync.contract.notes or ""):
        raise AssertionError("the sync fell back to the legacy GSPMD "
                             "assembly, not the mesh-resident builder")
    t0 = time.perf_counter()
    train = bundles.train.lower(mesh).compile()
    sync = bundles.sync.lower(mesh).compile()
    log(f"[four-chips] compile {time.perf_counter() - t0:.2f}s")
    crossing = collectives_crossing_axis(train.as_text(), mesh, "replica")
    audit = sync_collective_audit(sync.as_text(), mesh)
    log(f"[four-chips] train step replica collectives: {len(crossing)}; "
        f"sync replica collectives: {[op for op, _ in audit['replica']]}")
    if crossing or not audit["replica_allreduce_only"]:
        raise AssertionError("collective audit failed")
    require_kernels("four-chip train step", train.as_text(), TRAIN_KERNELS)
    require_kernels("four-chip sync", sync.as_text(), WINDOW_KERNELS)

    params = lm.init(jax.random.key(SEED))
    inner = jax.device_put(
        jax.tree.map(lambda x: jnp.broadcast_to(x[None], (K4,) + x.shape),
                     params), bundles.train.in_shardings[0])
    opt_init = jax.vmap(plan_optimizer(plan.optimizer).init)
    opt = jax.device_put(opt_init(inner), bundles.train.in_shardings[1])
    leaf = jax.tree.leaves(inner)[0]
    owners = sorted((s.index[0].start, s.device.id)
                    for s in leaf.addressable_shards)
    log(f"[four-chips] replica -> device {owners}")
    if len({d for _, d in owners}) != K4 or \
            [r for r, _ in owners] != list(range(K4)):
        raise AssertionError("replicas do not sit one per device")
    win = list(window_state_args(bundles))
    means, losses = [], []
    with mesh:
        for step in range(K4_STEPS):
            ks = jax.random.split(jax.random.key(1000 + step), 2)
            batch = {n: jax.random.randint(k, (K4, BATCH, SEQ), 0,
                                           cfg.vocab_size)
                     for n, k in zip(("tokens", "targets"), ks)}
            inner, opt, loss = train(inner, opt, batch)
            losses.append(float(np.mean(jax.device_get(loss))))
            if (step + 1) % H == 0:
                host = jax.device_get(inner)
                means.append(jax.tree.map(
                    lambda x: np.mean(np.asarray(x, np.float32), axis=0),
                    host))
                res = sync(inner, *win)
                inner = res[0]
                count, nidx, wa, cycle = res[3:7]
                win = [res[1], res[2], count, nidx, cycle]
    log("[four-chips] losses " + " ".join(f"{x:.4f}" for x in losses))
    if not all(np.isfinite(losses)):
        raise AssertionError(f"non-finite loss: {losses}")
    # After 2 syncs with I=2, W̄ is the mean of the last pre-sync
    # replicas and W̿ the mean of the two W̄. The chip sums the four
    # replicas in f32 in another order than numpy (at most three
    # roundings), halves the window total, then rounds to the leaf's
    # dtype: allow MEAN_ULPS ulps of that dtype.
    wbar = means[-1]
    wbb = jax.tree.map(lambda a, b: (a + b) / 2, means[0], means[1])
    for label, got, want in (
            ("W̄ (restart)", jax.tree.map(lambda x: x[0],
                                         jax.device_get(inner)), wbar),
            ("W̿", jax.device_get(wa), wbb)):
        worst = 0.0
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            ulp = np.abs(w) * float(jnp.finfo(g.dtype).eps) + 1e-30
            err = np.abs(np.asarray(g, np.float32) - w) / ulp
            worst = max(worst, float(np.max(err)))
        log(f"[four-chips] {label} vs host numpy mean of the replicas: "
            f"worst {worst:.3f} ulp of the leaf dtype (limit {MEAN_ULPS})")
        if not worst <= MEAN_ULPS:
            raise AssertionError(f"{label} off the host mean")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the mesh-native K=4 phase (needs four "
                         "chips)")
    args = ap.parse_args()

    root = os.path.dirname(os.path.abspath(__file__))
    src = os.path.join(root, "src")
    if not os.path.isdir(os.path.join(src, "repro", "kernels")):
        print("chip_smoke.py: no src/repro beside this script; run it from "
              "a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, src)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke.py: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 2
    from repro.common.compile_cache import use_compile_cache
    log(f"[device] {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"jax {jax.__version__}; compile cache {use_compile_cache()}")

    cfg = granite()
    if args.four_chips:
        if len(devices) < 4:
            print(f"--four-chips needs 4 devices, found {len(devices)}",
                  file=sys.stderr)
            return 2
        four_chip_phase(cfg, devices)
    else:
        lm, wa, ws = train_phase(cfg, dev)
        serve_phase(lm, wa, ws, dev)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
