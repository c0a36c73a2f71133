"""Per-architecture smoke tests (assignment deliverable f).

Reduced same-family variants (≤2 layers, d_model ≤ 512, ≤4 experts): one
forward + one optimizer step + one decode step on CPU, asserting output
shapes and finiteness.
"""
import jax
import jax.numpy as jnp
import pytest

from conftest import make_lm_batch
from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.models.registry import build_model
from repro.optim import apply_updates, sgd


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_and_train_step(arch):
    cfg = get_smoke_config(arch)
    assert cfg.n_layers <= 2 and cfg.d_model <= 512
    if cfg.n_experts:
        assert cfg.n_experts <= 4
    lm = build_model(cfg)
    params = lm.init(jax.random.key(0))
    batch = make_lm_batch(cfg)

    logits, aux = lm.apply(params, batch)
    if cfg.family == "audio":
        assert logits.shape == batch["targets"].shape + (cfg.vocab_size,)
    else:
        assert logits.shape == batch["targets"].shape + (cfg.vocab_size,)
    assert bool(jnp.all(jnp.isfinite(logits)))

    opt = sgd(momentum=0.9)
    opt_state = opt.init(params)
    (loss, metrics), grads = jax.value_and_grad(lm.loss, has_aux=True)(
        params, batch)
    assert bool(jnp.isfinite(loss))
    updates, opt_state = opt.update(grads, opt_state, params, 0.1)
    new_params = apply_updates(params, updates)
    moved = sum(float(jnp.sum(jnp.abs(a - b)))
                for a, b in zip(jax.tree.leaves(new_params),
                                jax.tree.leaves(params)))
    assert moved > 0
    for leaf in jax.tree.leaves(new_params):
        assert bool(jnp.all(jnp.isfinite(leaf)))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_decode_step_shapes(arch):
    cfg = get_smoke_config(arch)
    lm = build_model(cfg)
    params = lm.init(jax.random.key(0))
    B = 2
    cache, dims = lm.init_cache(B, 16)
    tok = (jnp.zeros((B,), jnp.int32) if cfg.family != "audio"
           else jnp.zeros((B, cfg.n_codebooks), jnp.int32))
    logits, cache2 = lm.decode_step(params, cache, tok)
    expected = ((B, cfg.vocab_size) if cfg.family != "audio"
                else (B, cfg.n_codebooks, cfg.vocab_size))
    assert logits.shape == expected
    assert bool(jnp.all(jnp.isfinite(logits)))
    assert int(cache2["pos"]) == 1


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_full_config_matches_assignment(arch):
    """The exact assigned hyperparameters (no allocation)."""
    cfg = get_config(arch)
    expected = {
        "qwen2-moe-a2.7b": (24, 2048, 16, 16, 1408, 151936, 60, 4),
        "internvl2-1b": (24, 896, 14, 2, 4864, 151655, 0, 0),
        "xlstm-125m": (12, 768, 4, 4, 0, 50304, 0, 0),
        "granite-moe-1b-a400m": (24, 1024, 16, 8, 512, 49155, 32, 8),
        "hymba-1.5b": (32, 1600, 25, 5, 5504, 32001, 0, 0),
        "granite-3-2b": (40, 2048, 32, 8, 8192, 49155, 0, 0),
        "stablelm-12b": (40, 5120, 32, 8, 13824, 100352, 0, 0),
        "command-r-35b": (40, 8192, 64, 8, 22528, 256000, 0, 0),
        "gemma2-27b": (46, 4608, 32, 16, 36864, 256000, 0, 0),
        "musicgen-medium": (48, 1536, 24, 24, 6144, 2048, 0, 0),
    }[arch]
    got = (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_ff,
           cfg.vocab_size, cfg.n_experts, cfg.top_k)
    assert got == expected
    assert cfg.source


def test_full_preset_cuts_only_depth():
    """``--preset full --n-layers N``: published widths, depth cut, the
    cut reported in ``reduced``."""
    from repro.configs import get_preset
    full = get_config("granite-3-2b")
    cfg, reduced = get_preset("granite-3-2b", "full", 2)
    assert cfg == full.with_(n_layers=2)
    assert reduced == {"n_layers": (full.n_layers, 2)}
    assert get_preset("granite-3-2b", "full") == (full, {})
    assert get_preset("granite-3-2b")[1] is None
    with pytest.raises(ValueError):
        get_preset("granite-3-2b", "full", full.n_layers + 1)
