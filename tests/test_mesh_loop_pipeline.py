"""The mesh-native HWA loop (``launch/train.py`` ``run_mesh_native``)
reads each step's losses one step behind and builds its batches on the
devices: on four CPU host devices, every recorded loss is still its own
step's, the outer-sync count is right, only the reads that must come
before the next dispatch stall, and the batch program makes
``loop_batch``'s rows in the train program's batch sharding.

The loop runs in a subprocess (this file run as a script), because the
host device count is set before JAX is imported:

  python tests/test_mesh_loop_pipeline.py <out.json>
"""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
H = 3                   # sync period; every run is two cycles
pytestmark = pytest.mark.timeout(900)


def _drive(out_path: str) -> None:
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import glob
    import tempfile

    import jax
    import numpy as np

    from chipbench import bench
    from repro.launch.train import run_mesh_native
    from repro.resilience.check import _mesh_args
    from repro.resilience.session import CheckpointSession
    from repro.train.trainer import SPAN_LOSS_READ

    loop_batch = bench.load_module("drivers", "mesh").loop_batch
    result = {}

    def recorder(name):
        rec = result[name] = {"losses": {}, "batch_equal": {},
                              "batch_sharding": {}}

        def on_step(step, live):
            rec["losses"][step] = float(np.mean(jax.device_get(
                live["losses"])))
            if step < 4:
                want = loop_batch(step, 4, 4, 16, 128)
                rec["batch_equal"][step] = all(
                    np.array_equal(jax.device_get(live["batch"][n]), want[n])
                    for n in want)
                (_, _, b_sh), _ = live["programs"]["train"].input_shardings
                rec["batch_sharding"][step] = all(
                    live["batch"][n].sharding.is_equivalent_to(b_sh[n], 3)
                    for n in want)
        return rec, on_step

    def summary(rec, out):
        rec.update({k: out[k] for k in ("history", "final_loss", "cycles",
                                        "syncs", "stalled_reads")})

    with tempfile.TemporaryDirectory() as d:
        rec, on_step = recorder("flat")
        with jax.profiler.trace(os.path.join(d, "profile")):
            summary(rec, run_mesh_native(
                _mesh_args(k=4, steps=2 * H, sync_period=H), on_step=on_step))
        xplane, = glob.glob(os.path.join(d, "profile", "**", "*.xplane.pb"),
                            recursive=True)
        reads = []
        for plane in jax.profiler.ProfileData.from_file(xplane).planes:
            for line in plane.lines:
                reads += [(ev.start_ns, dict(ev.stats)) for ev in line.events
                          if ev.name == SPAN_LOSS_READ]
        rec["loss_reads"] = [(s["step"], s["of_step"])
                             for _, s in sorted(reads, key=lambda e: e[0])]

    rec, on_step = recorder("resilient")
    summary(rec, run_mesh_native(
        _mesh_args(k=4, steps=2 * H, sync_period=H, resilient=True),
        on_step=on_step))

    # a checkpoint at each sync, and a last step off the sync boundary
    with tempfile.TemporaryDirectory() as d:
        rec, on_step = recorder("checkpoint")
        summary(rec, run_mesh_native(
            _mesh_args(k=4, steps=2 * H + 1, sync_period=H, checkpoint_dir=d,
                       checkpoint_every=H), on_step=on_step))
        session = CheckpointSession(d)
        rec["meta"] = {s: {k: session.meta(s)[k] for k in ("loss", "cycle")}
                       for s in (H, 2 * H)}
    with open(out_path, "w") as f:
        json.dump(result, f)


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    out = tmp_path_factory.mktemp("pipeline") / "runs.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run([sys.executable, os.path.abspath(__file__), str(out)],
                       capture_output=True, text=True, timeout=850, env=env)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    doc = json.loads(out.read_text())
    for rec in doc.values():             # json keys are strings
        for k in ("losses", "batch_equal", "batch_sharding"):
            rec[k] = {int(s): v for s, v in rec[k].items()}
    return doc


@pytest.mark.parametrize("name", ["flat", "resilient", "checkpoint"])
def test_each_recorded_loss_is_its_own_steps(runs, name):
    rec = runs[name]
    assert rec["history"]
    for h in rec["history"]:
        assert h["loss"] == rec["losses"][h["step"] - 1], h


@pytest.mark.parametrize("name", ["flat", "resilient", "checkpoint"])
def test_final_loss_is_the_last_steps(runs, name):
    rec = runs[name]
    assert rec["final_loss"] == rec["losses"][max(rec["losses"])]


def test_history_cycle_counts_the_outer_syncs(runs):
    rec = runs["flat"]
    assert [h["cycle"] for h in rec["history"]] == [1, 2]
    assert rec["cycles"] == rec["syncs"] == 2


def test_a_flat_run_never_stalls_on_a_read(runs):
    assert runs["flat"]["stalled_reads"] == 0


def test_a_resilient_run_stalls_at_least_once_a_sync(runs):
    rec = runs["resilient"]
    assert rec["stalled_reads"] >= rec["syncs"] == 2


def test_checkpoints_keep_their_steps_loss_and_cycle(runs):
    """A checkpoint's ``meta`` holds its own step's loss and the outer
    syncs done; the reads before each save (the sync's ``cycle``) and
    the last step's loss read (off a sync boundary) are the stalls."""
    rec = runs["checkpoint"]
    for s, meta in rec["meta"].items():
        assert meta == {"loss": rec["losses"][int(s) - 1],
                        "cycle": int(s) // H}
    assert rec["stalled_reads"] == 3


def test_loss_reads_name_the_step_they_read(runs):
    """Step n's losses are read inside step n+1, after its dispatch, or
    inside step n once its sync is queued."""
    want = []
    for step in range(2 * H):
        if step % H:
            want.append((step, step - 1))
        if (step + 1) % H == 0:
            want.append((step, step))
    assert [tuple(r) for r in runs["flat"]["loss_reads"]] == want


@pytest.mark.parametrize("step", range(4))
def test_the_batch_program_makes_loop_batchs_rows(runs, step):
    assert runs["flat"]["batch_equal"][step]


@pytest.mark.parametrize("step", range(4))
def test_the_batch_is_in_the_train_programs_sharding(runs, step):
    assert runs["flat"]["batch_sharding"][step]


if __name__ == "__main__":
    _drive(sys.argv[1])
