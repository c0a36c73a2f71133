"""Trainer integration: every method runs; HWA improves over its inner
weights; loss decreases (paper's core empirical claims at micro scale)."""
import jax
import pytest

from repro.core import HWAConfig
from repro.data import DataPipeline, make_markov_lm_dataset
from repro.models import build_model
from repro.models.types import ModelConfig
from repro.train import TrainConfig, Trainer, lm_task

TINY = ModelConfig(name="tiny", family="dense", n_layers=2, d_model=32,
                   n_heads=2, n_kv_heads=2, d_ff=64, vocab_size=32,
                   attn_impl="naive", remat="none", dtype="float32")


def make(method, steps=48, K=2, H=8, I=3):
    lm = build_model(TINY)
    ds = make_markov_lm_dataset(vocab=32, seq_len=32, n_train=256,
                                n_test=64, seed=0)
    k = K if method in ("hwa", "online", "pmsgd") else 1
    pipe = DataPipeline(ds, batch_size=8, n_replicas=k, seed=0)
    tc = TrainConfig(method=method, total_steps=steps, batch_size=8,
                     base_lr=0.5, eval_every=16,
                     hwa=HWAConfig(n_replicas=k, sync_period=H, window=I),
                     swa_start_frac=0.5, swa_lr=0.1)
    return Trainer(lm_task(lm, pipe), tc)


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
