"""Shared benchmark harness.

CPU-feasible proxy for the paper's CIFAR protocol: a 2-layer transformer
LM on a synthetic 2nd-order-learnable Markov task with a real train/test
generalization gap (DESIGN.md §8 deviation 1). Every method uses the same
budget, data and init seed; HWA uses H = one epoch (paper default) and
I = WINDOW.
"""
from __future__ import annotations

import time

from repro.core import HWAConfig
from repro.data import DataPipeline, make_markov_lm_dataset
from repro.models import build_model
from repro.models.types import ModelConfig
from repro.train import TrainConfig, Trainer, lm_task

VOCAB = 64
SEQ = 48
STEPS = 512
BATCH = 8
N_TRAIN = 256          # 32 steps/epoch -> 16 epochs/sync cycles
WINDOW = 4
BASE_LR = 0.5

TINY = ModelConfig(name="bench-lm", family="dense", n_layers=2, d_model=48,
                   n_heads=4, n_kv_heads=2, d_ff=96, vocab_size=VOCAB,
                   attn_impl="naive", remat="none", dtype="float32")


def run_method(method: str, *, k: int = 2, window: int = WINDOW,
               sync_period: int = 0, steps: int = STEPS, seed: int = 0,
               base_lr: float = BASE_LR, swa_lr: float = 0.1,
               eval_views: bool = False, model: ModelConfig = TINY):
    lm = build_model(model)
    ds = make_markov_lm_dataset(vocab=model.vocab_size, seq_len=SEQ,
                                n_train=N_TRAIN, n_test=128, seed=0)
    kk = k if method in ("hwa", "online", "pmsgd") else 1
    pipe = DataPipeline(ds, batch_size=BATCH, n_replicas=kk, seed=seed)
    tc = TrainConfig(method=method, total_steps=steps, batch_size=BATCH,
                     base_lr=base_lr, seed=seed, swa_lr=swa_lr,
                     swa_start_frac=0.6,
                     eval_every=max(N_TRAIN // BATCH, 1),
                     hwa=HWAConfig(n_replicas=kk, sync_period=sync_period,
                                   window=window))
    t0 = time.time()
    out = Trainer(lm_task(lm, pipe), tc).run(eval_views=eval_views)
    out["seconds"] = time.time() - t0
    out["us_per_step"] = out["seconds"] / steps * 1e6
    return out


def csv_row(name: str, us: float, derived: str) -> str:
    return f"{name},{us:.1f},{derived}"


def run_forced_device_worker(worker_file: str, flag: str, *,
                             error_row: str, print_fn=print,
                             n_devices: int = 8, timeout: int = 600):
    """Re-exec ``worker_file`` with ``flag`` under N forced host devices
    and return its last-stdout-line JSON dict. A failed worker prints an
    ``error_row`` CSV row and raises ``RuntimeError``, so the benchmark
    run fails with it.

    Mesh benchmarks must run the device-hungry part in a subprocess so
    the forced host platform never leaks into the benchmark process;
    this is the shared driver (benchmarks/mesh_comm.py,
    benchmarks/kernel_bench.py). The worker only introspects HLO on host
    devices, so it runs with ``JAX_PLATFORMS=cpu``: a parent that has
    imported JAX may hold the chip, and a child reaching for it would
    fail or hang.
    """
    import json
    import os
    import subprocess
    import sys

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = \
        f"--xla_force_host_platform_device_count={n_devices}"
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.path.join(root, "src") + os.pathsep + root + \
        os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, os.path.abspath(worker_file), flag],
        capture_output=True, text=True, env=env, timeout=timeout, cwd=root)
    if proc.returncode != 0:
        tail = (proc.stderr or proc.stdout)[-160:].replace(
            "\n", " ").replace(",", ";")
        print_fn(csv_row(error_row, 0.0, tail))
        raise RuntimeError(f"{os.path.basename(worker_file)} worker exited "
                           f"{proc.returncode}: {tail}")
    return json.loads(proc.stdout.strip().splitlines()[-1])
