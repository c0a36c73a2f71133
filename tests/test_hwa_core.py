"""HWA state machine: the paper's Algorithms 1 & 2, exactly."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common.pytree import tree_mean_axis0, tree_stack
from repro.core import (HWAConfig, hwa_init, hwa_inner_step, hwa_sync,
                        broadcast_to_replicas, online_average,
                        replica_divergence, window_init, window_update,
                        window_average)
from repro.optim import sgd


def params_like(seed=0):
    k = jax.random.key(seed)
    k1, k2 = jax.random.split(k)
    return {"w": jax.random.normal(k1, (4, 3)),
            "b": jax.random.normal(k2, (7,))}


def test_online_average_is_mean():
    ps = [params_like(i) for i in range(3)]
    stacked = tree_stack(ps)
    outer = online_average(stacked)
    for leaf, *leaves in zip(jax.tree.leaves(outer),
                             *[jax.tree.leaves(p) for p in ps]):
        np.testing.assert_allclose(leaf, np.mean(leaves, axis=0), rtol=1e-6)


def test_broadcast_restart_resets_all_replicas():
    outer = params_like()
    inner = broadcast_to_replicas(outer, 4)
    for leaf, o in zip(jax.tree.leaves(inner), jax.tree.leaves(outer)):
        assert leaf.shape == (4,) + o.shape
        for k in range(4):
            np.testing.assert_array_equal(leaf[k], o)


@pytest.mark.parametrize("use_kernel", [False, True])
def test_window_matches_bruteforce(use_kernel):
    """Ring slide-window == mean of the last I outer weights (Alg. 2)."""
    I = 4
    p0 = params_like()
    ws = window_init(p0, I)
    outers = [params_like(100 + t) for t in range(9)]
    for t, outer in enumerate(outers):
        ws, wa = window_update(ws, outer, use_kernel=use_kernel)
        lo = max(0, t + 1 - I)
        expect = tree_mean_axis0(tree_stack(outers[lo:t + 1]))
        for a, b in zip(jax.tree.leaves(wa), jax.tree.leaves(expect)):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_streaming_window_matches_exact_until_full():
    I = 5
    p0 = params_like()
    ws_r = window_init(p0, I, "ring")
    ws_s = window_init(p0, I, "streaming")
    for t in range(I):
        outer = params_like(200 + t)
        ws_r, wa_r = window_update(ws_r, outer)
        ws_s, wa_s = window_update(ws_s, outer)
        for a, b in zip(jax.tree.leaves(wa_r), jax.tree.leaves(wa_s)):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def quad_loss(params, batch):
    """Simple convex loss with per-batch noise."""
    target, noise = batch
    l = sum(jnp.sum((p - target + noise) ** 2)
            for p in jax.tree.leaves(params))
    return l, {"loss": l, "acc": jnp.zeros(())}


def test_k1_i1_hwa_equals_plain_sgd():
    opt = sgd(momentum=0.9)
    cfg = HWAConfig(n_replicas=1, sync_period=2, window=1)
    p0 = params_like()
    state = hwa_init(cfg, p0, opt)
    # plain SGD reference
    ref_p, ref_o = p0, opt.init(p0)
    for step in range(6):
        batch = (0.5, 0.01 * step)
        kbatch = (jnp.full((1,), 0.5), jnp.full((1,), 0.01 * step))
        state, _ = hwa_inner_step(cfg, state, kbatch, quad_loss, opt, 0.05)
        (_, _), g = jax.value_and_grad(quad_loss, has_aux=True)(ref_p, batch)
        upd, ref_o = opt.update(g, ref_o, ref_p, 0.05)
        ref_p = jax.tree.map(lambda p, u: p + u, ref_p, upd)
        if (step + 1) % 2 == 0:
            state, _ = hwa_sync(cfg, state)
    for a, b in zip(jax.tree.leaves(state.wa), jax.tree.leaves(ref_p)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_sync_restart_effect_and_divergence_metric():
    """After sync all replicas are equal; before sync they differ (they saw
    different batches) — the paper's Fig. 12 'restart' mechanics."""
    opt = sgd(momentum=0.0)
    cfg = HWAConfig(n_replicas=3, sync_period=4, window=2)
    state = hwa_init(cfg, params_like(), opt)
    for step in range(4):
        kbatch = (jnp.arange(3.0), jnp.arange(3.0) * 0.1)
        state, _ = hwa_inner_step(cfg, state, kbatch, quad_loss, opt, 0.05)
    w = state.inner["w"]
    assert float(jnp.max(jnp.abs(w[0] - w[1]))) > 1e-6
    assert float(replica_divergence(state.inner)) > 0
    state, metrics = hwa_sync(cfg, state)
    assert "replica_divergence" not in metrics
    w = state.inner["w"]
    assert float(jnp.max(jnp.abs(w[0] - w[1]))) == 0.0
    assert int(state.cycle) == 1


def test_sparse_window_stride():
    """§III-B: with stride J only every J-th cycle enters the window."""
    opt = sgd()
    cfg = HWAConfig(n_replicas=1, sync_period=1, window=2, window_stride=2)
    state = hwa_init(cfg, params_like(), opt)
    counts = []
    for _ in range(5):
        state = jax.tree.map(lambda x: x, state)
        # force distinct inner weights per cycle
        state.inner["w"] = state.inner["w"] + 1.0
        state, _ = hwa_sync(cfg, state)
        counts.append(int(state.window_state.count))
    assert counts == [1, 1, 2, 2, 2]
