"""Operations and bytes counted from shapes."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))                    # the repository root
from chipbench import bench, flops
from chipbench.reference.granite import Dims


@pytest.fixture(scope="module")
def granite():
    return Dims.from_config(bench.read_json(
        bench.HERE + "/configs/granite-3-2b.json"))


def test_granite_one_layer_is_981_mflop_per_token(granite):
    assert granite.n_layers == 1
    per_token = flops.model_flops_per_token(granite, 1024)
    assert per_token == pytest.approx(981e6, rel=0.01)
    assert flops.matmul_params(granite) == 161_486_848


def test_fused_sync_bytes_follow_the_architecture_table():
    # docs/ARCHITECTURE.md: the fused sync reads (K+2)·N, writes 3·N f32
    for k, n in ((2, 8192), (4, 262_168_576)):
        assert flops.fused_sync_bytes(k, n) == ((k + 2) * n + 3 * n) * 4


def test_flash_counts_causal_products(granite):
    one = flops.causal_matmul_flops(granite, 1024)
    assert one == 2 * 32 * 64 * (1024 * 1025 // 2)
    assert flops.flash_call_flops(granite, 1024, "dkv", 8) == 4 * one * 8


def test_packed_size_is_whole_tiles():
    import jax
    import jax.numpy as jnp
    tree = {"a": jax.ShapeDtypeStruct((3, 5), jnp.bfloat16),
            "b": jax.ShapeDtypeStruct((9000,), jnp.float32)}
    assert flops.packed_size(tree) == 2 * 8192
