"""Trainer integration: every method runs; HWA improves over its inner
weights; loss decreases (paper's core empirical claims at micro scale);
the loop's profiler spans and the programs' names that the chip
benchmark's trace readers find."""
import dataclasses
import glob
import os

import jax
import pytest

from repro.core import HWAConfig, hwa_init
from repro.data import DataPipeline, make_markov_lm_dataset
from repro.models import build_model
from repro.models.types import ModelConfig
from repro.train import TrainConfig, Trainer, lm_task
from repro.train.trainer import (SPAN_CALLBACK, SPAN_CHECKPOINT,
                                 SPAN_EVALUATE, SPAN_INNER_STEP, SPAN_STEP,
                                 SPAN_SYNC, SPANS)

TINY = ModelConfig(name="tiny", family="dense", n_layers=2, d_model=32,
                   n_heads=2, n_kv_heads=2, d_ff=64, vocab_size=32,
                   attn_impl="naive", remat="none", dtype="float32")


def make(method, steps=48, K=2, H=8, I=3, **tc_changes):
    lm = build_model(TINY)
    ds = make_markov_lm_dataset(vocab=32, seq_len=32, n_train=256,
                                n_test=64, seed=0)
    k = K if method in ("hwa", "online", "pmsgd") else 1
    pipe = DataPipeline(ds, batch_size=8, n_replicas=k, seed=0)
    tc = TrainConfig(method=method, total_steps=steps, batch_size=8,
                     base_lr=0.5, eval_every=16,
                     hwa=HWAConfig(n_replicas=k, sync_period=H, window=I),
                     swa_start_frac=0.5, swa_lr=0.1)
    return Trainer(lm_task(lm, pipe), dataclasses.replace(tc, **tc_changes))


@pytest.mark.parametrize("method", ["base", "ca", "swa", "ema", "lookahead",
                                    "sam", "online", "pmsgd", "hwa"])
def test_method_runs_and_decreases_loss(method):
    out = make(method).run()
    assert len(out["history"]) >= 2
    first, last = out["history"][0], out["history"][-1]
    assert last["test_loss"] < first["test_loss"] + 0.1
    assert out["final"]["test_loss"] < 4.0   # ln(32) ≈ 3.46 at random


def test_hwa_views_recorded():
    out = make("hwa").run(eval_views=True)
    rec = out["history"][-1]
    assert "inner_loss" in rec and "outer_loss" in rec
    # W̿ should not be worse than the raw inner weights late in training
    assert rec["test_loss"] <= rec["inner_loss"] + 0.2


def test_best_tracking():
    out = make("hwa").run()
    assert out["best"]["test_acc"] >= max(
        h["test_acc"] for h in out["history"]) - 1e-9


def test_on_step_sees_every_step_before_its_sync():
    """``on_step`` runs after each inner step and before that step's sync
    consumes the (donated) state."""
    seen = []

    def on_step(step, state, metrics):
        seen.append((step, int(state.step), int(state.cycle)))

    make("hwa", steps=16, H=8).run(on_step=on_step)
    assert [s for s, _, _ in seen] == list(range(16))
    assert [n for _, n, _ in seen] == list(range(1, 17))
    assert [c for _, _, c in seen] == [0] * 8 + [1] * 8


# ------------------------------------------------------- profiler spans

K2H2 = dict(steps=8, K=2, H=2, eval_every=0, checkpoint_every=4)


def host_spans(xplane: str) -> dict:
    """{span name: [(start_ns, end_ns, ids)]} of the loop's spans on the
    profiler's host plane, by start."""
    from jax.profiler import ProfileData
    out = {name: [] for name in SPANS}
    for plane in ProfileData.from_file(xplane).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in out:
                    out[ev.name].append((ev.start_ns,
                                         ev.start_ns + ev.duration_ns,
                                         dict(ev.stats)))
    return {k: sorted(v, key=lambda e: e[0]) for k, v in out.items()}


@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    """K=2, H=2, 8 steps, W̿ evaluated every sync, a checkpoint every 4
    steps, under the profiler."""
    root = tmp_path_factory.mktemp("spans")
    trainer = make("hwa", checkpoint_dir=str(root / "ckpt"), **K2H2)
    with jax.profiler.trace(str(root / "profile")):
        out = trainer.run(on_step=lambda step, state, metrics: None)
    xplane, = glob.glob(os.path.join(root, "profile", "**", "*.xplane.pb"),
                        recursive=True)
    return out, host_spans(xplane)


def test_each_boundary_of_the_loop_is_a_span_with_its_ids(traced_run):
    _, spans = traced_run
    ids = lambda name, key: [e[2][key] for e in spans[name]]
    assert ids(SPAN_STEP, "step_num") == list(range(8))
    assert ids(SPAN_INNER_STEP, "step") == list(range(8))
    assert set(ids(SPAN_INNER_STEP, "tokens")) == {2 * 8 * 32}    # K·B·S
    assert ids(SPAN_CALLBACK, "step") == list(range(8))
    assert ids(SPAN_SYNC, "cycle") == [1, 2, 3, 4]
    assert ids(SPAN_EVALUATE, "step") == [1, 3, 5, 7]     # every sync
    assert ids(SPAN_CHECKPOINT, "step") == [3, 7]
    steps = {e[2]["step_num"]: e for e in spans[SPAN_STEP]}
    for name in (SPAN_INNER_STEP, SPAN_CALLBACK, SPAN_SYNC, SPAN_EVALUATE,
                 SPAN_CHECKPOINT):
        for start, end, kw in spans[name]:
            step = kw["step"] if "step" in kw else 2 * kw["cycle"] - 1
            outer = steps[step]
            assert outer[0] <= start and end <= outer[1], (name, kw)


def test_spans_change_no_result(traced_run, tmp_path):
    traced, _ = traced_run
    out = make("hwa", checkpoint_dir=str(tmp_path), **K2H2).run(
        on_step=lambda step, state, metrics: None)
    assert out["history"] == traced["history"]
    assert out["final"] == traced["final"]
    assert len(out["history"]) == 4


def test_programs_keep_the_names_the_trace_readers_find():
    """The chip benchmark finds the programs in a device trace by these
    names (``jit_<function>``)."""
    trainer = make("hwa", steps=2, H=2)
    params = trainer.task.init(jax.random.key(0))
    state = hwa_init(trainer.hwa_cfg, params, trainer.optimizer)
    inputs, targets = next(trainer.task.pipeline.eval_batches())
    lowered = {"jit_hwa_step": trainer.hwa_step.lower(state, 0),
               "jit_sync_step": trainer.sync_step.lower(state),
               "jit_eval_batch": trainer._eval_batch.lower(params, inputs,
                                                           targets)}
    for name, low in lowered.items():
        assert low.as_text().startswith(f"module @{name} "), name
