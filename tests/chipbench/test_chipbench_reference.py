"""The plain reference against the program at a size a CPU holds: the
same loss and gradients in float32, and a control (float8 matmuls) that
the comparison's limits catch."""
import functools
import os
import sys

import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))                    # the repository root
from chipbench import bench, compare, training
from chipbench.reference import granite

TINY = dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, vocab_size=256,
            num_hidden_layers=2)


@pytest.fixture(scope="module")
def setup():
    doc = dict(bench.read_json(bench.HERE + "/configs/granite-3-2b.json"),
               **TINY, torch_dtype="float32")
    doc["program"] = dict(doc["program"], attn_impl="naive", remat="none")
    dims = granite.Dims.from_config(doc)
    from repro.models.registry import build_model
    lm = build_model(training.model_config(doc))
    key = jax.random.key(3)
    params = jax.jit(functools.partial(granite.init_params, dims))(key)
    tok, tgt = granite.make_rows(jax.random.fold_in(key, 1), 4, 64,
                                 dims.vocab)
    return dims, lm, params, tok, tgt


def test_weights_have_the_program_layout(setup):
    dims, lm, params, _, _ = setup
    training.check_layout(lm, functools.partial(granite.init_params, dims),
                          jax.random.key(0))


def test_loss_and_gradients_match_the_program(setup):
    dims, lm, params, tok, tgt = setup
    with jax.default_matmul_precision("highest"):
        (want, _), g_prog = jax.value_and_grad(
            lambda p: lm.loss(p, {"tokens": tok, "targets": tgt}),
            has_aux=True)(params)
    got, g_ref = granite.loss_and_grad(dims, params, tok, tgt)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    a = np.asarray(granite.leaf_norms(g_ref))
    b = np.asarray(granite.leaf_norms(g_prog))
    assert compare.norm_gap(b, a) < 1e-4


def test_control_reads_far_above_the_reference(setup):
    dims, _, params, tok, tgt = setup
    f32, _ = granite.loss_and_grad(dims, params, tok, tgt)
    fp8, _ = granite.loss_and_grad(dims, params, tok, tgt, matmul="fp8")
    assert abs(float(fp8) - float(f32)) / float(f32) > 1e-3


def test_rows_follow_the_seed():
    key = jax.random.key(9)
    a = granite.make_rows(key, 3, 16, 100)
    b = granite.make_rows(key, 3, 16, 100)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert np.array_equal(a[0][:, 1:], a[1][:, :-1])   # targets: next token
    rows = granite.batch_rows(key, 1, 5, 8, 2)
    assert rows.shape == (2,) and len(set(np.asarray(rows))) == 2
