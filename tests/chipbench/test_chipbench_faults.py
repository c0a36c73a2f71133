"""A run with the timed path broken underneath comes out not correct.

Each test skips the harness's look for a chip and drives the rest of a
run at a size a CPU holds, through the cell's own driver, checks and
limits, with one fault planted in the program: a step that returns its
state unchanged, and half of the batch left out (the mean over the
rest). The control (the reference in float8, the sync's arithmetic in
bfloat16, in the program's place) comes out not correct too, and a sound
run correct.
"""
import os
import sys

import jax
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))                    # the repository root
from chipbench import bench, compare

# float32, so that a sound run's rounding sits far below the cell's
# limits (set for bfloat16 at the published widths) and only the planted
# fault can cross them
TINY = dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, vocab_size=256,
            torch_dtype="float32")
TRAINER = "granite-3-2b.k2-h4"


def tiny_config(cell: str, seed: int = 5) -> bench.Run:
    config = cell.rpartition(".")[0]
    entry = {"name": cell, "config": config}
    cfg = dict(bench.read_json(os.path.join(bench.HERE, "configs",
                                            f"{config}.json")), **TINY)
    cfg["program"] = dict(cfg["program"], attn_impl="naive")
    traffic = dict(bench.traffic_doc(cell), batch_per_replica=2, seq_len=32)
    if "train_rows" in traffic:
        traffic.update(train_rows=16, eval_rows=2, eval_every=8)
    return bench.Run(cell=entry, config=cfg, traffic=traffic, seed=seed,
                     seconds=0.0, devices=jax.devices(),
                     window=bench.Window(False))


def tiny_run(cell: str, seed: int = 5):
    r = tiny_config(cell, seed)
    out = bench.load_module("drivers", r.traffic["driver"]).run(r)
    out.free()
    out.verify()
    return r, out


def correct(r, out) -> bool:
    return all(c.ok for c in r.checks) and out.failed == 0


@pytest.fixture(scope="module")
def sound():
    return tiny_run(TRAINER)


def test_sound_run_is_correct(sound):
    r, out = sound
    assert correct(r, out), bench.checks_text(r.checks)


def test_sound_run_reports_its_throughput(sound):
    r, out = sound
    t = r.traffic
    assert out.tokens == out.attempted * t["replicas"] * \
        t["batch_per_replica"] * t["seq_len"] > 0
    assert out.end_to_end["train_tokens_per_s"] > 0


def test_a_new_seed_compiles_nothing_new():
    """The inner step closes over its rows: they come from the traffic's
    data seed, so two seeds compile the same program, and another data
    seed another one."""
    from repro.core.hwa import hwa_init
    drv = bench.load_module("drivers", "trainer")
    texts = []
    for seed, data_seed in ((5, 1), (2 ** 31 + 17, 1), (5, 2)):
        r = tiny_config(TRAINER, seed)
        r.traffic["data_seed"] = data_seed
        trainer, init, _ = drv.build(r)
        state = jax.eval_shape(lambda k: hwa_init(
            trainer.hwa_cfg, init(k), trainer.optimizer),
            jax.random.key(seed))
        texts.append(trainer.hwa_step.lower(state, 0).as_text())
    assert texts[0] == texts[1] != texts[2]


def test_control_is_not_correct(sound):
    r, out = sound
    info = out.info
    drv = bench.load_module("drivers", r.traffic["driver"])
    ref = drv.reference_readings(r, info["dims"], info["key"])
    fp8 = drv.reference_readings(r, info["dims"], info["key"], matmul="fp8")
    numbers = compare.training_gaps(fp8, ref)
    numbers.update(drv.sync_numbers(r.traffic["replicas"],
                                    r.traffic["window"],
                                    info["captured"]["sync"], control=True))
    checks = bench.checks(numbers, r.traffic["limits"])
    assert not all(c.ok for c in checks), bench.checks_text(checks)


def test_frozen_step_is_not_correct(monkeypatch):
    import repro.train.trainer as trainer_mod
    real = trainer_mod.hwa_inner_step

    def frozen(cfg, state, batches, loss_fn, optimizer, lr):
        new, metrics = real(cfg, state, batches, loss_fn, optimizer, lr)
        return state.__class__(**{**vars(state), "step": new.step}), metrics

    monkeypatch.setattr(trainer_mod, "hwa_inner_step", frozen)
    r, out = tiny_run(TRAINER)
    assert not correct(r, out)
    failed = {c.name for c in r.checks if not c.ok}
    assert "change_norm_gap" in failed, bench.checks_text(r.checks)


def test_half_batch_is_not_correct(monkeypatch):
    import repro.train.trainer as trainer_mod
    real = trainer_mod.hwa_inner_step

    def half(cfg, state, batches, loss_fn, optimizer, lr):
        b = jax.tree.leaves(batches)[0].shape[1]
        batches = jax.tree.map(lambda x: x[:, : b // 2], batches)
        return real(cfg, state, batches, loss_fn, optimizer, lr)

    monkeypatch.setattr(trainer_mod, "hwa_inner_step", half)
    r, out = tiny_run(TRAINER)
    assert not correct(r, out), bench.checks_text(r.checks)
