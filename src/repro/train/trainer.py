"""Training orchestration: HWA + every paper baseline under one loop.

Methods (paper §V experiment set):
  base      — SGD, step-decay LR ×0.1 every ``decay_every`` (paper Baseline)
  ca        — SGD, cosine LR over the whole budget
  swa       — offline WA: Stage I regular LR, Stage II constant sampling LR,
              running average of every-H checkpoints (SWA [15])
  ema       — exponential moving average of weights
  lookahead — Lookahead optimizer [32]
  sam       — sharpness-aware minimization [35]
  online    — low-frequency online WA only (HWA with I=1)
  pmsgd     — parallel mini-batch SGD (sync every step, K replicas)
  hwa       — the full method (K replicas, period H, window I)

The trainer evaluates the *method-appropriate* weights (W̿ for HWA, the
running average for SWA/EMA, slow weights for Lookahead) and tracks the
best snapshot (paper §IV-C early-stopping remark).
"""
from __future__ import annotations

import dataclasses
import functools
import math
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.profiler import StepTraceAnnotation, TraceAnnotation

from repro.core.baselines import (ema_init, ema_update, lookahead_init,
                                  lookahead_update, sam_gradient, swa_init,
                                  swa_params, swa_update)
from repro.core.hwa import HWAConfig, HWAState, hwa_init, hwa_inner_step, \
    hwa_sync
from repro.data.pipeline import DataPipeline
from repro.models.registry import LM
from repro.optim import (adamw, apply_updates, cosine_schedule, sgd,
                         step_decay_schedule, swa_constant_schedule)

PyTree = Any

# Profiler spans at the training loops' layer boundaries. They are
# ``jax.profiler`` annotations: they land on the profiler's host plane, on
# the clock its device events are mapped to, and record nothing when no
# profiler is active. Every span of a step sits inside that step's
# ``hwa.step`` (a StepTraceAnnotation, ``step_num``); the ids ride as
# keyword arguments: ``step`` is the loop's step index, ``cycle`` the
# syncs done with this one, ``tokens`` the input elements one step
# consumes (K·B·S for a language model). ``hwa.batch`` and
# ``hwa.loss_read`` are the mesh loop's (``launch/train.py``).
SPANS = (SPAN_STEP, SPAN_INNER_STEP, SPAN_SYNC, SPAN_EVALUATE,
         SPAN_CHECKPOINT, SPAN_CALLBACK, SPAN_BATCH, SPAN_LOSS_READ) = (
    "hwa.step", "hwa.inner_step", "hwa.sync", "hwa.evaluate",
    "hwa.checkpoint", "hwa.callback", "hwa.batch", "hwa.loss_read")


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    method: str = "hwa"
    total_steps: int = 1000
    batch_size: int = 16
    base_lr: float = 0.1
    optimizer: str = "sgd"          # sgd | adamw
    momentum: float = 0.9
    weight_decay: float = 5e-4
    decay_every_frac: float = 0.33  # step-decay interval (method=base)
    hwa: HWAConfig = HWAConfig()
    swa_start_frac: float = 0.75
    swa_lr: float = 0.05
    ema_decay: float = 0.99
    lookahead_k: int = 5
    lookahead_alpha: float = 0.5
    sam_rho: float = 0.05
    eval_every: int = 0             # 0 → every sync cycle
    seed: int = 0
    # preemption-safe checkpointing (resilience.CheckpointSession); the
    # data pipeline and schedules are stateless functions of (seed, step),
    # so restoring the saved state + step resumes bit-exactly
    checkpoint_dir: str = ""        # "" → no checkpointing
    checkpoint_every: int = 0       # steps between saves (0 → off)
    checkpoint_keep: int = 3        # retained checkpoints
    resume: bool = False            # restart from the newest intact save


@dataclasses.dataclass
class Task:
    init: Callable[[jax.Array], PyTree]
    loss_fn: Callable[[PyTree, Any], tuple[jax.Array, dict]]
    pipeline: DataPipeline
    name: str = "task"


def lm_task(lm: LM, pipeline: DataPipeline, name: str | None = None) -> Task:
    def loss_fn(params, batch):
        if isinstance(batch, tuple):
            batch = {"tokens": batch[0], "targets": batch[1]}
        return lm.loss(params, batch)
    return Task(init=lm.init, loss_fn=loss_fn, pipeline=pipeline,
                name=name or lm.cfg.name)


def _make_optimizer(tc: TrainConfig):
    if tc.optimizer == "adamw":
        return adamw(weight_decay=tc.weight_decay)
    return sgd(momentum=tc.momentum, weight_decay=tc.weight_decay)


def _make_schedule(tc: TrainConfig):
    if tc.method == "base":
        return step_decay_schedule(
            tc.base_lr, max(int(tc.total_steps * tc.decay_every_frac), 1))
    sched = cosine_schedule(tc.base_lr, tc.total_steps)
    if tc.method == "swa":
        return swa_constant_schedule(
            sched, int(tc.total_steps * tc.swa_start_frac), tc.swa_lr)
    return sched


class Trainer:
    def __init__(self, task: Task, tc: TrainConfig):
        self.task = task
        self.tc = tc
        self.optimizer = _make_optimizer(tc)
        self.schedule = _make_schedule(tc)
        self.is_parallel = tc.method in ("hwa", "online", "pmsgd")
        if tc.method == "online":
            self.hwa_cfg = dataclasses.replace(tc.hwa, window=1)
        elif tc.method == "pmsgd":
            self.hwa_cfg = dataclasses.replace(tc.hwa, sync_period=1, window=1)
        else:
            self.hwa_cfg = tc.hwa
        self.sync_period = self.hwa_cfg.sync_period or \
            task.pipeline.steps_per_epoch
        if tc.method == "pmsgd":
            self.sync_period = 1
        self._build_steps()

    # -------------------------------------------------------- jit steps

    def _build_steps(self):
        task, tc, opt = self.task, self.tc, self.optimizer
        loss_fn, sched = task.loss_fn, self.schedule

        # both steps donate the state: only the returned one stays live
        @functools.partial(jax.jit, donate_argnums=(0,))
        def hwa_step(state: HWAState, step):
            batches = task.pipeline.stacked_batch(step)
            return hwa_inner_step(self.hwa_cfg, state, batches, loss_fn,
                                  opt, sched(step))

        @functools.partial(jax.jit, donate_argnums=(0,))
        def sync_step(state: HWAState):
            return hwa_sync(self.hwa_cfg, state)

        @jax.jit
        def single_step(params, opt_state, step):
            batch = task.pipeline.replica_batch(0, step)
            if tc.method == "sam":
                (loss, metrics), grads = sam_gradient(loss_fn, params, batch,
                                                      rho=tc.sam_rho)
            else:
                (loss, metrics), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params, batch)
            updates, opt_state = opt.update(grads, opt_state, params,
                                            sched(step))
            return apply_updates(params, updates), opt_state, loss, metrics

        @jax.jit
        def eval_batch(params, inputs, targets):
            loss, metrics = loss_fn(params, {"tokens": inputs,
                                             "targets": targets})
            return metrics["loss"], metrics.get("acc", jnp.zeros(()))

        # public: a caller may lower them to inspect the compiled step
        self.hwa_step, self.sync_step = hwa_step, sync_step
        self._single_step, self._eval_batch = single_step, eval_batch
        self._swa_update = jax.jit(swa_update)
        self._ema_update = jax.jit(ema_update)
        self._lookahead_update = jax.jit(lookahead_update)

    # ------------------------------------------------------------ eval

    def evaluate(self, params) -> dict:
        losses, accs = [], []
        for inputs, targets in self.task.pipeline.eval_batches():
            l, a = self._eval_batch(params, inputs, targets)
            losses.append(float(l))
            accs.append(float(a))
        return {"test_loss": sum(losses) / max(len(losses), 1),
                "test_acc": sum(accs) / max(len(accs), 1)}

    # ------------------------------------------------------------- run

    def run(self, eval_views: bool = False, log: bool = False,
            on_step: Callable | None = None) -> dict:
        """Train for ``total_steps``. For the K-replica methods,
        ``on_step(step, state, metrics)`` is called after every inner
        step with the live :class:`HWAState`, before that step's sync
        (if any) consumes it. It runs synchronously, so a caller can time
        steps or copy a pre-sync state out to check the sync. Under a
        ``jax.profiler`` trace each step records the spans of :data:`SPANS`.
        """
        tc = self.tc
        key = jax.random.key(tc.seed)
        params = self.task.init(key)
        history = []
        best = {"test_acc": -1.0, "test_loss": float("inf"), "step": 0}
        eval_every = tc.eval_every or self.sync_period
        inputs = self.task.pipeline.dataset.train_inputs
        step_tokens = self.task.pipeline.batch_size * math.prod(
            inputs.shape[1:])

        def record(step, train_loss, eval_params, views=None):
            rec = {"step": step, "train_loss": float(train_loss)}
            rec.update(self.evaluate(eval_params))
            if views:
                for name, p in views.items():
                    v = self.evaluate(p)
                    rec[f"{name}_loss"] = v["test_loss"]
                    rec[f"{name}_acc"] = v["test_acc"]
            history.append(rec)
            if rec["test_acc"] > best["test_acc"]:
                best.update({"test_acc": rec["test_acc"],
                             "test_loss": rec["test_loss"], "step": step})
            if log:
                print(f"[{self.task.name}/{tc.method}] step {step} "
                      f"train {rec['train_loss']:.4f} "
                      f"test {rec['test_loss']:.4f} acc {rec['test_acc']:.4f}")
            return rec

        session = None
        if tc.checkpoint_dir and tc.checkpoint_every > 0:
            from repro.resilience.session import CheckpointSession
            session = CheckpointSession(tc.checkpoint_dir,
                                        keep=tc.checkpoint_keep)
        if session is None and tc.resume:
            raise ValueError("resume=True needs checkpoint_dir and "
                             "checkpoint_every set")
        if session is not None and not self.is_parallel:
            raise ValueError("checkpointing covers the K-replica methods "
                             f"(hwa/online/pmsgd), not {tc.method!r}")

        if self.is_parallel:
            state = hwa_init(self.hwa_cfg, params, self.optimizer)
            train_loss = jnp.zeros(())
            start_step = 0
            if session is not None and tc.resume:
                latest = session.latest_intact()
                if latest is not None:
                    state = session.load(latest, "hwa", state)
                    meta = session.meta(latest)
                    start_step = int(meta["step"])
                    history = list(meta.get("history", []))
                    best.update(meta.get("best", {}))
                    train_loss = jnp.asarray(meta.get("train_loss", 0.0))
                    if log:
                        print(f"[{self.task.name}/{tc.method}] resumed "
                              f"from step {start_step} "
                              f"({session.step_dir(start_step)})")
            tokens = self.hwa_cfg.n_replicas * step_tokens
            for step in range(start_step, tc.total_steps):
                with StepTraceAnnotation(SPAN_STEP, step_num=step):
                    with TraceAnnotation(SPAN_INNER_STEP, step=step,
                                         tokens=tokens):
                        state, metrics = self.hwa_step(state, step)
                    train_loss = metrics["loss"]
                    if on_step is not None:
                        with TraceAnnotation(SPAN_CALLBACK, step=step):
                            on_step(step, state, metrics)
                    if (step + 1) % self.sync_period == 0:
                        views = None
                        if eval_views:
                            # snapshot BEFORE the sync resets inner <- outer
                            views = {
                                "inner": jax.tree.map(lambda x: x[0],
                                                      state.inner),
                                "outer": jax.tree.map(
                                    lambda x: jnp.mean(x, 0).astype(x.dtype),
                                    state.inner),
                            }
                        cycle = (step + 1) // self.sync_period
                        with TraceAnnotation(SPAN_SYNC, cycle=cycle):
                            state, _ = self.sync_step(state)
                        if cycle % max(eval_every // self.sync_period,
                                       1) == 0:
                            with TraceAnnotation(SPAN_EVALUATE, step=step):
                                record(step + 1, train_loss, state.wa, views)
                    if session is not None and \
                            (step + 1) % tc.checkpoint_every == 0:
                        # HWAState is one registered-dataclass pytree (the
                        # WindowState layout rides in its meta fields), so
                        # a single named tree round-trips everything
                        # bit-exactly
                        with TraceAnnotation(SPAN_CHECKPOINT, step=step):
                            session.save(step + 1, {"hwa": state},
                                         meta={"step": step + 1,
                                               "history": history,
                                               "best": dict(best),
                                               "train_loss":
                                                   float(train_loss)})
            final_params = state.wa
        else:
            opt_state = self.optimizer.init(params)
            swa_state = swa_init(params) if tc.method == "swa" else None
            ema_state = (ema_init(params, tc.ema_decay)
                         if tc.method == "ema" else None)
            la_state = (lookahead_init(params, tc.lookahead_k,
                                       tc.lookahead_alpha)
                        if tc.method == "lookahead" else None)
            swa_start = int(tc.total_steps * tc.swa_start_frac)
            swa_period = self.task.pipeline.steps_per_epoch
            train_loss = jnp.zeros(())
            for step in range(tc.total_steps):
                with StepTraceAnnotation(SPAN_STEP, step_num=step):
                    with TraceAnnotation(SPAN_INNER_STEP, step=step,
                                         tokens=step_tokens):
                        params, opt_state, train_loss, _ = \
                            self._single_step(params, opt_state, step)
                    if tc.method == "ema":
                        ema_state = self._ema_update(ema_state, params)
                    if tc.method == "lookahead" and \
                            (step + 1) % tc.lookahead_k == 0:
                        la_state, params = self._lookahead_update(la_state,
                                                                  params)
                    if (tc.method == "swa" and step + 1 > swa_start
                            and (step + 1) % swa_period == 0):
                        swa_state = self._swa_update(swa_state, params)
                    if (step + 1) % eval_every == 0:
                        eval_params = params
                        if tc.method == "swa" and int(swa_state.n) > 0:
                            eval_params = swa_params(swa_state, params)
                        elif tc.method == "ema":
                            eval_params = jax.tree.map(
                                lambda a, p: a.astype(p.dtype),
                                ema_state.avg, params)
                        with TraceAnnotation(SPAN_EVALUATE, step=step):
                            record(step + 1, train_loss, eval_params)
            final_params = params
            if tc.method == "swa" and int(swa_state.n) > 0:
                final_params = swa_params(swa_state, params)
            elif tc.method == "ema":
                final_params = jax.tree.map(lambda a, p: a.astype(p.dtype),
                                            ema_state.avg, params)

        final = self.evaluate(final_params)
        return {"history": history, "best": best, "final": final,
                "params": final_params}
