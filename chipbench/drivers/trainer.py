"""Driver ``trainer``: the single-device HWA ``Trainer`` (K vmapped
replicas on one chip), run through its own loop, ``Trainer.run``.

The weights come from ``--seed``. The token rows and the order in which
each replica draws them come from the traffic's fixed ``data_seed``: the
Trainer's inner step closes over its data pipeline, so the rows are
constants of the compiled step, and rows drawn from ``--seed`` would
compile it anew for every seed.

Set-up builds one ``Trainer`` and drives it once from the seed through
``checked_syncs`` syncs: that compiles the inner step, the sync and the
W̿ evaluation, and reads what the comparison needs (the first steps'
losses, the first gradient and the parameters' change, and a sample of
one sync's inputs and outputs). The window then runs ``Trainer.run`` on
the same ``Trainer`` again from the seed. ``on_step`` adds no device
work: it opens the window after the first step (``block_until_ready``);
at each cycle boundary it waits for the previous boundary's loss, so the
host runs at most one cycle ahead of the device and the queue never
empties; and at the first boundary once ``seconds`` have passed it waits
for the device and closes the window, which so holds whole H-step cycles
with their syncs and W̿ evaluations.

After the window, with the program's state freed, the float32 reference
(``reference/granite.py``) retraces the first steps of every replica and
the sync's arithmetic, and ``correct`` follows from the comparison.
"""
from __future__ import annotations

import functools
import gc
import time

import numpy as np

from chipbench import bench, compare, flops, training
from chipbench.reference import granite

class _Stop(Exception):
    """Raised from ``on_step`` to end a ``Trainer.run``."""


def cosine_lr(base: float, total: int, step: int) -> float:
    """The cosine schedule HWA trains under (paper §V), from ``base``."""
    frac = min(max(step / max(total, 1), 0.0), 1.0)
    return base * 0.5 * (1.0 + np.cos(np.pi * frac))


def build(r: bench.Run):
    """The cell's ``Trainer`` on rows from the traffic's ``data_seed``,
    with the seeded weights' ``init``; and the model's sizes."""
    import jax

    from repro.core.hwa import HWAConfig
    from repro.data.pipeline import DataPipeline
    from repro.data.synthetic import SyntheticDataset
    from repro.models.registry import build_model
    from repro.train.trainer import Task, TrainConfig, Trainer, lm_task

    t = r.traffic
    B, K, opt = t["batch_per_replica"], t["replicas"], t["optimizer"]
    dims = granite.Dims.from_config(r.config)
    lm = build_model(training.model_config(r.config))
    init = jax.jit(functools.partial(granite.init_params, dims))
    training.check_layout(lm, init, jax.random.key(r.seed))
    rows, n = data_rows(t, dims), t["train_rows"]
    ds = SyntheticDataset(train_inputs=rows[0][:n], train_targets=rows[1][:n],
                          test_inputs=rows[0][n:], test_targets=rows[1][n:])
    pipe = DataPipeline(ds, batch_size=B, n_replicas=K, seed=t["data_seed"])
    task = Task(init=init, loss_fn=lm_task(lm, pipe).loss_fn, pipeline=pipe,
                name=r.cell["name"])
    tc = TrainConfig(method="hwa", total_steps=t["schedule_steps"],
                     batch_size=B, base_lr=opt["lr"], optimizer=opt["name"],
                     momentum=opt["momentum"],
                     weight_decay=opt["weight_decay"],
                     eval_every=t["eval_every"], seed=r.seed,
                     hwa=HWAConfig(n_replicas=K, sync_period=t["sync_period"],
                                   window=t["window"],
                                   use_kernels=t["use_kernels"]))
    r.log(f"[trainer] K={K} H={t['sync_period']} I={t['window']} "
          f"{B}x{t['seq_len']} tokens per replica per step, "
          f"{lm.cfg.n_layers} layers, attn {lm.cfg.attn_impl}")
    return Trainer(task, tc), init, dims


def run(r: bench.Run) -> bench.Outcome:
    import jax
    import jax.numpy as jnp

    t = r.traffic
    K, H, I = t["replicas"], t["sync_period"], t["window"]
    B, S = t["batch_per_replica"], t["seq_len"]
    key = jax.random.key(r.seed)
    trainer, init, dims = build(r)
    r.phase("trainer built")

    # ---- set-up: one drive from the seed, read what the check needs
    params_abs = jax.eval_shape(init, key)
    idx, offsets = training.sample_points(jax.random.fold_in(key, 2),
                                          params_abs, training.SYNC_SAMPLE)
    sync_step = t["checked_syncs"] * H - 1     # on_step before that sync
    per_leaf = jax.jit(lambda tree: jax.vmap(granite.leaf_norms)(tree))
    change = jax.jit(lambda inner, start: jax.vmap(
        granite.change_norms, in_axes=(0, None))(inner, start))
    take = jax.jit(training.gather_leaves, static_argnums=(2,))
    prog = {"losses": [], "grad": None, "change": None}
    sync, shapes = {}, {}

    def packed(ws, leaves_idx):
        pos = jnp.asarray(np.concatenate(
            [o + i for o, i in zip(offsets, leaves_idx)]))
        return (jnp.take(ws.ring, pos, axis=1), jnp.take(ws.total, pos),
                int(ws.count), int(ws.next_idx))

    def setup_step(step, state, metrics):
        if step < 3:
            prog["losses"].append(metrics["per_replica_loss"])
        if step == 0:
            prog["grad"] = per_leaf(state.inner_opt["mu"])
            shapes["state"] = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                               sharding=x.sharding), state)
        if step == 2:                       # wa is still the initial W
            prog["change"] = change(state.inner, state.wa)
        if step == sync_step:
            sync["inner"] = take(jax.tree.leaves(state.inner), idx, 1)
            sync["pre"] = packed(state.window_state, idx)
        if step == sync_step + 1:           # window state, W̿ after it
            sync["post"] = packed(state.window_state, idx)
            sync["wa"] = take(jax.tree.leaves(state.wa), idx, 0)
            trainer.evaluate(state.wa)      # compiles the W̿ evaluation
            raise _Stop

    try:
        trainer.run(on_step=setup_step)
    except _Stop:
        pass
    prog = jax.device_get(prog)
    sync = jax.device_get(sync)
    gc.collect()
    r.phase("set-up drive and its reads")

    # ---- the window
    box = {"boundary": None}

    def window_step(step, state, metrics):
        if step == 0:
            jax.block_until_ready(state)
            r.t_window = time.perf_counter()
            r.window.open()
        elif step % H == 0:
            if box["boundary"] is not None:
                jax.block_until_ready(box["boundary"])
            box["boundary"] = metrics["loss"]
            if time.perf_counter() - r.window.t0 >= r.seconds:
                jax.block_until_ready(state)
                r.window.close()
                box.update(steps=step, loss=metrics["loss"])
                raise _Stop

    try:
        trainer.run(on_step=window_step)
    except _Stop:
        pass
    steps = box["steps"]
    finite = bool(np.isfinite(float(box["loss"])))
    hlo = {}
    if r.window.trace:      # which HLO names the trace readers look for
        hlo = {"train_step": trainer.hwa_step.lower(
                   shapes["state"], 0).compile().as_text(),
               "sync": trainer.sync_step.lower(
                   shapes["state"]).compile().as_text()}

    def free():
        box.clear()
        gc.collect()

    tokens = steps * K * B * S

    def verify():
        r.checks.extend(check_training(r, dims, key, prog))
        r.checks.extend(bench.checks(sync_numbers(K, I, sync),
                                     t["limits"]))

    return bench.Outcome(
        tokens=tokens, attempted=steps, failed=0 if finite else 1,
        end_to_end={"train_tokens_per_s": tokens / r.window.seconds},
        programs={"train_step": "jit_hwa_step", "sync": "jit_sync_step",
                  "eval": "jit_eval_batch"},
        free=free, verify=verify,
        info={"replicas": K, "replicas_per_chip": K,
              "sequences_per_chip": K * B, "tokens_per_step": K * B * S,
              "dims": dims,
              "batch": B, "seq_len": S, "key": key,
              "padded": flops.packed_size(params_abs),
              "captured": {"prog": prog, "sync": sync}, "hlo": hlo})


def data_rows(t: dict, dims):
    """(inputs, targets) of the traffic's train and eval rows, from its
    ``data_seed``."""
    import jax
    return jax.jit(granite.make_rows, static_argnums=(1, 2, 3))(
        jax.random.fold_in(jax.random.key(t["data_seed"]), 1),
        t["train_rows"] + t["eval_rows"], t["seq_len"], dims.vocab)


def reference_readings(r: bench.Run, dims, key, matmul="f32", fault=""):
    """The reference's losses, first-gradient and change norms for every
    replica over the first three steps of the cell's feed."""
    import jax
    t = r.traffic
    K, B, n = t["replicas"], t["batch_per_replica"], t["train_rows"]
    opt = t["optimizer"]
    params0 = jax.jit(functools.partial(granite.init_params, dims))(key)
    tok, tgt = data_rows(t, dims)
    order = jax.random.key(t["data_seed"])
    lrs = [cosine_lr(opt["lr"], t["schedule_steps"], s) for s in range(3)]
    out = []
    for k in range(K):
        batches = []
        for s in range(3):
            rows = granite.batch_rows(order, k, s, n, B)
            batches.append((tok[rows], tgt[rows]))
        losses, grad, change = granite.train_readings(
            dims, params0, batches, lrs, opt["momentum"],
            opt["weight_decay"], matmul=matmul, fault=fault)
        out.append({"losses": losses, "grad": grad, "change": change})
    return {"replicas": out}


def check_training(r: bench.Run, dims, key, prog) -> list:
    mine = compare.program_readings(prog)
    ref = reference_readings(r, dims, key)
    checks = bench.checks(compare.training_gaps(mine, ref),
                          r.traffic["limits"])
    if not all(c.ok for c in checks):
        for line in compare.leaf_table(mine, ref, training.leaf_names(
                dims)):
            r.log(f"[check] {line}")
    return checks


def sync_numbers(K: int, I: int, sync: dict, control: bool = False
                 ) -> dict:
    """The sync against a copy of the fused sync's arithmetic
    (``kernels/ref.py``): mean = sum × (1/K), total += mean − evicted
    slot, W̿ = total × 1/count, elementwise in float32 in the same order,
    so the program has to match it bit for bit. Counts the sampled
    elements of W̄ (the written ring slot), the total and W̿ that differ.
    ``control`` puts the same arithmetic in bfloat16 in the program's
    place."""
    stacked = np.concatenate([np.asarray(x, np.float32).reshape(K, -1)
                              for x in sync["inner"]], axis=1)
    ring, total, count, nidx = sync["pre"]
    ring2, total2, _, _ = sync["post"]
    full = count >= I
    inv = np.float32(1.0) / np.float32(min(count + 1, I))

    def arithmetic(dt):
        mean = np.sum(stacked.astype(dt), axis=0, dtype=dt) * dt(1.0 / K)
        tot = (total.astype(dt) + mean) - ring[nidx].astype(dt) * dt(full)
        return mean, tot, tot * dt(inv)

    mean, want_total, want_wa = arithmetic(np.float32)
    got_wa = [np.asarray(x).reshape(-1) for x in sync["wa"]]
    if control:
        import ml_dtypes
        c_mean, total2, c_wa = arithmetic(ml_dtypes.bfloat16)
        ring2 = ring2.copy()
        ring2[nidx] = c_mean
        got_wa, start = [], 0
        for leaf in sync["wa"]:
            n = np.asarray(leaf).size
            got_wa.append(c_wa[start:start + n].astype(leaf.dtype))
            start += n
    bad = int(np.sum(ring2[nidx] != mean)) \
        + int(np.sum(total2 != want_total))
    start = 0
    for leaf in got_wa:                    # W̿ in each leaf's own dtype
        want = want_wa[start:start + leaf.size].astype(leaf.dtype)
        bad += int(np.sum(leaf != want))
        start += leaf.size
    return {"sync_mismatches": bad}
