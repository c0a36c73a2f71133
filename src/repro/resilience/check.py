"""fault-check: deterministic fault-injection harness over the
resilience stack. Importable core of ``tools/fault_check.py`` (which
only sets XLA_FLAGS for the forced host devices before jax loads).

Every leg is a deterministic end-to-end scenario with a hard pass/fail
verdict — no flakiness budget, no retries at the harness level:

  masked-parity    all-healthy alive-masked mean is BITWISE identical to
                   the plain K-mean (tree level and packed-buffer level)
  nan-replica      a NaN-poisoned replica is quarantined at sync; the
                   run reaches the final step with finite W̿
  resume-exact     checkpoint at N/2, rerun with --resume: final state
                   bit-identical to the uninterrupted run
  kill-mid-save    a simulated preemption truncating the manifest
                   mid-write leaves a torn, skipped checkpoint; the
                   session falls back to the previous intact one
  corrupt-fallback bit-flip the newest checkpoint: CRC verification
                   rejects it and --resume recomputes from the previous
                   intact save, bit-exactly matching the clean run
  transient-io     injected OSErrors during a save are retried with
                   capped backoff; exhaustion surfaces the error
  store-partial    a truncated outer_*.npz is skipped (with a warning)
                   by the window average; retention keeps the last N
  session-gc       the checkpoint session retains ``keep`` newest steps
                   and the newest survivor always verifies

``REPRO_FAULT_SMOKE=1`` (or ``--smoke``) runs the PR-lane subset,
leaving the full set to the nightly job.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import tempfile
import warnings
from typing import Callable

REQUIRED_DEVICES = 8

#: env var selecting the PR-lane smoke subset
SMOKE_ENV = "REPRO_FAULT_SMOKE"


@dataclasses.dataclass
class Leg:
    """One deterministic fault scenario."""
    name: str
    run: Callable[[], str]         # returns a detail line; raises on fail
    smoke: bool = False


# ------------------------------------------------------------- helpers


def _require_devices():
    import jax
    if len(jax.devices()) < REQUIRED_DEVICES:
        raise RuntimeError(
            f"fault-check needs {REQUIRED_DEVICES} devices for the "
            f"mesh legs (found {len(jax.devices())}); run via "
            "tools/fault_check.py, which sets "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8 before "
            "importing jax")


def _mesh_args(**kw):
    """An argparse.Namespace for ``launch.train.run_mesh_native`` with
    the launcher's defaults (tiny smoke config)."""
    ns = argparse.Namespace(
        arch="granite-3-2b", preset="smoke", n_layers=0, k=2, tp=1,
        fsdp=False, sync_tree="flat",
        pods=0, outer_every=2, window=3, seq_len=16, batch_size=4,
        lr=0.3, seed=0, steps=8, sync_period=2, attn_impl="",
        resilient=False, max_param_rms=0.0, inject_nan="",
        wa_dtype="f32", comms_dtype="f32",
        checkpoint_dir="", checkpoint_every=0, keep=3, resume=False)
    for k, v in kw.items():
        setattr(ns, k, v)
    return ns


def _trees_equal(a, b) -> bool:
    import jax
    import numpy as np
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    if len(la) != len(lb):
        return False
    for x, y in zip(la, lb):
        xa, ya = np.asarray(x), np.asarray(y)
        if xa.dtype != ya.dtype or xa.shape != ya.shape:
            return False
        if not np.array_equal(xa, ya):
            return False
    return True


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


def _demo_tree(seed: int = 0):
    import numpy as np
    rng = np.random.default_rng(seed)
    return {"w": rng.standard_normal((5, 7)).astype(np.float32),
            "b": rng.standard_normal((11,)).astype(np.float32)}


# ---------------------------------------------------------------- legs


def leg_masked_parity() -> str:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.common.pytree import tree_mean_axis0
    from repro.core.online import halving_sum_axis0
    from repro.resilience.health import masked_mean_axis0, renormalized_inv

    rng = np.random.default_rng(0)
    K = 4
    tree = {
        "w": jnp.asarray(rng.standard_normal((K, 3, 5)).astype(np.float32)),
        "b": jnp.asarray(rng.standard_normal((K, 7)).astype(np.float32)),
        # integer leaf (the adamw step count): masked path must keep
        # the exact dtype-faithful selection
        "count": jnp.arange(K, dtype=jnp.int32),
    }
    all_alive = jnp.ones((K,), jnp.bool_)
    got = jax.jit(masked_mean_axis0)(tree, all_alive)
    want = tree_mean_axis0(tree)
    _check(_trees_equal(got, want),
           "all-alive masked_mean_axis0 != tree_mean_axis0 (tree level)")

    # one dead replica: result is finite and ≈ the mean of the survivors
    dead = 2
    poisoned = dict(tree)
    poisoned["w"] = tree["w"].at[dead].set(jnp.nan)
    alive = all_alive.at[dead].set(False)
    got = jax.jit(masked_mean_axis0)(poisoned, alive)
    _check(bool(jnp.all(jnp.isfinite(got["w"]))),
           "masked mean leaked the NaN replica")
    keep = [i for i in range(K) if i != dead]
    ref = np.asarray(tree["w"], np.float64)[keep].mean(0)
    _check(float(np.abs(np.asarray(got["w"], np.float64) - ref).max())
           < 1e-6, "masked mean deviates from the survivors' mean")

    # packed-buffer level: the exact formula the mesh sync runs
    inv_pin = renormalized_inv(jnp.float32(K), K)
    _check(np.asarray(inv_pin).tobytes()
           == np.float32(1.0 / K).tobytes(),
           "renormalized_inv does not pin the trace-time f32 1/K")
    sbuf = jnp.asarray(rng.standard_normal((K, 257)).astype(np.float32))
    plain = halving_sum_axis0(sbuf) * jnp.float32(1.0 / K)
    masked = halving_sum_axis0(
        jnp.where(all_alive[:, None], sbuf, jnp.float32(0.0))) * inv_pin
    _check(np.array_equal(np.asarray(plain), np.asarray(masked)),
           "all-alive packed masked mean != plain packed mean")
    return "all-alive masked mean bitwise == plain mean (tree + packed)"


def leg_nan_replica() -> str:
    _require_devices()
    from repro.launch.train import run_mesh_native

    out = run_mesh_native(_mesh_args(steps=8, resilient=True,
                                     inject_nan="2:1"))
    _check(out["wa_finite"], "W̿ went non-finite despite the alive mask")
    _check(out["k_alive_min"] == 1,
           f"expected the poisoned sync to see k_alive=1, got "
           f"{out['k_alive_min']}")
    final = [h for h in out["history"] if h.get("sync") == "outer"][-1]
    _check(final["k_alive"] == 2,
           f"re-seeded replica did not recover (final k_alive "
           f"{final['k_alive']})")
    return (f"poisoned replica quarantined (k_alive dipped to "
            f"{out['k_alive_min']}, recovered to {final['k_alive']}), "
            f"W̿ finite at step {out['history'][-1]['step']}")


def leg_resume_exact() -> str:
    _require_devices()
    from repro.launch.train import run_mesh_native

    clean = run_mesh_native(_mesh_args(steps=8))
    with tempfile.TemporaryDirectory() as d:
        run_mesh_native(_mesh_args(steps=4, checkpoint_dir=d,
                                   checkpoint_every=4))
        resumed = run_mesh_native(_mesh_args(steps=8, checkpoint_dir=d,
                                             checkpoint_every=4,
                                             resume=True))
    _check(_trees_equal(clean["_state"], resumed["_state"]),
           "resumed final state differs from the uninterrupted run")
    return "checkpoint@4 + --resume reproduces the 8-step run bit-exactly"


def leg_kill_mid_save() -> str:
    from repro.resilience.faults import KillAt, SimulatedCrash
    from repro.resilience.session import CheckpointSession

    t4, t8 = _demo_tree(4), _demo_tree(8)
    with tempfile.TemporaryDirectory() as d:
        crash = CheckpointSession(
            d, fault_injector=KillAt("manifest_write", occurrence=2,
                                     truncate_frac=0.4))
        crash.save(4, {"state": t4})
        died = False
        try:
            crash.save(8, {"state": t8})
        except SimulatedCrash:
            died = True
        _check(died, "KillAt did not fire on the second manifest write")

        fresh = CheckpointSession(d)
        ok8, _ = fresh.verify(8)
        _check(not ok8, "torn step-8 checkpoint verifies")
        _check(fresh.latest_intact() == 4,
               f"latest_intact {fresh.latest_intact()} != 4")
        _check(_trees_equal(fresh.load(4, "state", t4), t4),
               "fallback checkpoint does not round-trip")
        fresh.save(8, {"state": t8})      # post-crash rewrite heals it
        _check(fresh.latest_intact() == 8, "healed step 8 not intact")
    return ("preemption mid-manifest leaves a torn dir; session falls "
            "back to step 4 and heals on the next save")


def leg_corrupt_fallback() -> str:
    _require_devices()
    from repro.launch.train import run_mesh_native
    from repro.resilience.faults import flip_bit
    from repro.resilience.session import CheckpointSession

    clean = run_mesh_native(_mesh_args(steps=8))
    with tempfile.TemporaryDirectory() as d:
        run_mesh_native(_mesh_args(steps=8, checkpoint_dir=d,
                                   checkpoint_every=4))
        sess = CheckpointSession(d)
        _check(sess.latest_intact() == 8, "expected intact step 8")
        flip_bit(os.path.join(sess.step_dir(8), "inner.npz"))
        _check(sess.latest_intact() == 4,
               "CRC verification accepted the bit-flipped checkpoint")
        resumed = run_mesh_native(_mesh_args(steps=8, checkpoint_dir=d,
                                             checkpoint_every=4,
                                             resume=True))
    _check(_trees_equal(clean["_state"], resumed["_state"]),
           "resume-from-fallback differs from the uninterrupted run")
    return ("bit-flipped newest checkpoint rejected by CRC; resume "
            "recomputed from step 4 bit-exactly")


def leg_transient_io() -> str:
    from repro.resilience.faults import InjectedIOError, TransientIO
    from repro.resilience.session import CheckpointSession

    tree = _demo_tree(1)
    with tempfile.TemporaryDirectory() as d:
        sess = CheckpointSession(
            d, retries=3, backoff=0.0,
            fault_injector=TransientIO("array_write", times=2),
            sleep=lambda s: None)
        sess.save(4, {"state": tree})
        _check(sess.io_retries == 2,
               f"expected 2 retried OSErrors, counted {sess.io_retries}")
        _check(sess.latest_intact() == 4, "retried save not intact")
    with tempfile.TemporaryDirectory() as d:
        sess = CheckpointSession(
            d, retries=2, backoff=0.0,
            fault_injector=TransientIO("array_write", times=10),
            sleep=lambda s: None)
        exhausted = False
        try:
            sess.save(4, {"state": tree})
        except InjectedIOError:
            exhausted = True
        _check(exhausted, "retry exhaustion did not surface the OSError")
        _check(CheckpointSession(d).latest_intact() is None,
               "failed save left an 'intact' checkpoint")
    return "2 transient OSErrors retried to success; exhaustion surfaces"


def leg_store_partial() -> str:
    import numpy as np

    from repro.checkpoint.store import OuterWeightStore
    from repro.resilience.faults import truncate_file

    like = _demo_tree(2)
    with tempfile.TemporaryDirectory() as d:
        store = OuterWeightStore(d)
        trees = {c: _demo_tree(10 + c) for c in (1, 2, 3)}
        for c, t in trees.items():
            store.save(c, t)
        truncate_file(store._path(2), frac=0.5)
        bad = store.verify()
        _check(list(bad) == [2], f"verify flagged {sorted(bad)} != [2]")
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            avg = store.window_average(3, window=3, like=like)
        _check(any("skipping unreadable" in str(w.message)
                   for w in caught), "no skip warning for the torn cycle")
        ref = {k: ((trees[1][k].astype(np.float64)
                    + trees[3][k].astype(np.float64)) / 2)
               for k in like}
        _check(max(float(np.abs(np.asarray(avg[k], np.float64)
                                - ref[k]).max()) for k in like) < 1e-6,
               "window average did not renormalize over readable cycles")
    with tempfile.TemporaryDirectory() as d:
        store = OuterWeightStore(d, keep_last=2)
        for c in range(1, 5):
            store.save(c, like)
        _check(store.cycles() == [3, 4],
               f"retention kept {store.cycles()} != [3, 4]")
    return "torn outer checkpoint skipped+warned; keep_last=2 retains [3,4]"


def leg_session_gc() -> str:
    from repro.resilience.session import CheckpointSession

    tree = _demo_tree(3)
    with tempfile.TemporaryDirectory() as d:
        sess = CheckpointSession(d, keep=2)
        for step in (4, 8, 12):
            sess.save(step, {"state": tree})
        _check(sess.steps() == [8, 12],
               f"gc kept {sess.steps()} != [8, 12]")
        _check(sess.latest_intact() == 12, "newest survivor not intact")
    return "keep=2 retains [8, 12]; newest survivor verifies"


def default_legs() -> list[Leg]:
    return [
        Leg("masked-parity", leg_masked_parity, smoke=True),
        Leg("nan-replica", leg_nan_replica),
        Leg("resume-exact", leg_resume_exact, smoke=True),
        Leg("kill-mid-save", leg_kill_mid_save, smoke=True),
        Leg("corrupt-fallback", leg_corrupt_fallback),
        Leg("transient-io", leg_transient_io, smoke=True),
        Leg("store-partial", leg_store_partial),
        Leg("session-gc", leg_session_gc),
    ]


# -------------------------------------------------------------- driver


def run_leg(leg: Leg) -> dict:
    from repro.resilience.faults import SimulatedCrash
    try:
        detail = leg.run()
        return {"ok": True, "detail": detail}
    except SimulatedCrash as e:     # a leg leaked its own injected crash
        return {"ok": False, "error": f"leaked SimulatedCrash: {e}"}
    except Exception as e:          # noqa: BLE001
        return {"ok": False, "error": f"{type(e).__name__}: {e}"}


def run_fault_check(legs: list[Leg] | None = None, smoke: bool = False,
                    log=print) -> dict:
    legs = default_legs() if legs is None else legs
    if smoke:
        legs = [l for l in legs if l.smoke]
    results = {}
    for leg in legs:
        log(f"fault-check: {leg.name} ...")
        results[leg.name] = run_leg(leg)
        status = "ok" if results[leg.name]["ok"] else "FAIL"
        log(f"fault-check: {leg.name}: {status} — "
            f"{results[leg.name].get('detail', results[leg.name].get('error'))}")
    return {"legs": results, "smoke": smoke,
            "ok": all(r["ok"] for r in results.values())}


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="fault_check",
        description="Deterministic fault-injection harness: NaN "
                    "poisoning, kill-mid-save, bit flips, transient IO — "
                    "each leg a hard pass/fail scenario.")
    ap.add_argument("--smoke", action="store_true",
                    help=f"PR-lane subset (also via {SMOKE_ENV}=1)")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="write the machine-readable report here")
    ap.add_argument("--only", metavar="SUBSTR", default=None,
                    help="run only legs whose name contains SUBSTR")
    ap.add_argument("--list", action="store_true",
                    help="list leg names and exit")
    args = ap.parse_args(argv)

    smoke = args.smoke or os.environ.get(SMOKE_ENV) == "1"
    legs = default_legs()
    if args.list:
        for l in legs:
            print(("[smoke] " if l.smoke else "        ") + l.name)
        return 0
    if args.only:
        legs = [l for l in legs if args.only in l.name]
        if not legs:
            print(f"no fault leg matches {args.only!r}", file=sys.stderr)
            return 2
    report = run_fault_check(legs, smoke=smoke)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(report, f, indent=2)
            f.write("\n")
        print(f"report written to {args.json}")
    n = len(report["legs"])
    if report["ok"]:
        print(f"fault-check: ALL_OK ({n} legs)")
        return 0
    failed = [k for k, r in report["legs"].items() if not r["ok"]]
    print(f"fault-check: FAILED ({len(failed)}/{n}): {', '.join(failed)}")
    return 1


if __name__ == "__main__":
    sys.exit(main())
