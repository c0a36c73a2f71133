"""repro.common.compat: the jax 0.9 mesh/tree entry points (``auto`` →
``axis_names`` for partial-manual shard_map, ``Auto`` mesh axes), plus a
checkpoint bf16 round-trip regression through the tree shim."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from repro.common import compat
from repro.checkpoint import load_pytree, save_pytree


# ----------------------------------------------------- tree_flatten_with_path

def test_tree_flatten_with_path_matches_tree_util():
    tree = {"a": [jnp.ones((2,)), jnp.zeros((3,))], "b": {"c": jnp.ones(())}}
    got_flat, got_def = compat.tree_flatten_with_path(tree)
    want_flat, want_def = jax.tree_util.tree_flatten_with_path(tree)
    assert got_def == want_def
    assert [p for p, _ in got_flat] == [p for p, _ in want_flat]


def test_tree_flatten_with_path_resolves_new_api_when_present():
    assert compat.tree_flatten_with_path is jax.tree.flatten_with_path


# ------------------------------------------------------------ mesh context

def _mesh_1d():
    return compat.make_mesh((1,), ("data",))


def test_mesh_context_leaves_new_arrays_uncommitted():
    """Compiled steps run inside ``with mesh:``: arrays made there stay
    uncommitted, so a step compiled for other shardings accepts them."""
    mesh = _mesh_1d()
    sh = NamedSharding(mesh, P("data"))
    step = jax.jit(lambda v: v * 2, in_shardings=sh).lower(
        jax.ShapeDtypeStruct((4,), jnp.float32)).compile()
    with mesh:
        x = jnp.ones((4,))
        assert not x.committed
        np.testing.assert_array_equal(np.asarray(step(x)), 2 * np.ones(4))


def test_set_mesh_commits_new_arrays():
    """Why the launchers do not use ``jax.set_mesh``: it commits new
    arrays to the mesh, replicated (pins the behaviour the compat
    docstring relies on)."""
    mesh = _mesh_1d()
    with jax.set_mesh(mesh):
        x = jnp.ones((4,))
    assert x.committed and isinstance(x.sharding, NamedSharding)


# ----------------------------------------------------------------- make_mesh

def test_make_mesh_new_api_branch():
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    assert mesh.axis_names == ("data", "model")
    assert dict(mesh.shape) == {"data": 1, "model": 1}


def test_make_mesh_fallback_branch():
    """An explicit device list is honoured; every axis is Auto."""
    dev = jax.devices()[:1]
    mesh = compat.make_mesh((1, 1), ("data", "model"), devices=dev)
    assert list(mesh.devices.flat) == dev
    assert mesh.axis_types == (AxisType.Auto, AxisType.Auto)


# ----------------------------------------------------------------- shard_map

def test_shard_map_old_keywords():
    mesh = _mesh_1d()
    f = compat.shard_map(lambda x: x * 2, mesh, in_specs=(P(),),
                         out_specs=P(), check_rep=False)
    np.testing.assert_array_equal(np.asarray(f(jnp.ones((4,)))),
                                  2 * np.ones((4,)))


def test_shard_map_check_vma_spelling():
    """check_vma is accepted as the same switch as check_rep."""
    mesh = _mesh_1d()
    f = compat.shard_map(lambda x: x + 1, mesh, in_specs=(P(),),
                         out_specs=P(), check_vma=False)
    np.testing.assert_array_equal(np.asarray(f(jnp.zeros((4,)))),
                                  np.ones((4,)))


@pytest.mark.parametrize("auto,manual", [
    (frozenset(), {"data", "model"}),
    (frozenset({"model"}), {"data"}),
])
def test_shard_map_auto_maps_to_axis_names(monkeypatch, auto, manual):
    """``auto`` names the partitioner's axes; jax.shard_map is handed
    the complement as its manual ``axis_names``."""
    seen = {}
    real = jax.shard_map

    def spy(f, **kw):
        seen.update(kw)
        return real(f, **kw)

    monkeypatch.setattr(jax, "shard_map", spy)
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    f = compat.shard_map(lambda x: x * 3, mesh, in_specs=(P("data"),),
                         out_specs=P("data"), auto=auto)
    np.testing.assert_array_equal(np.asarray(jax.jit(f)(jnp.ones((2,)))),
                                  3 * np.ones((2,)))
    assert seen["axis_names"] == manual


def test_shard_map_rejects_unknown_auto_axis():
    with pytest.raises(ValueError, match="not mesh axes"):
        compat.shard_map(lambda x: x, _mesh_1d(), in_specs=(P(),),
                         out_specs=P(), auto=frozenset({"model"}))


def test_shard_map_rejects_all_auto():
    with pytest.raises(ValueError, match="at least one manual axis"):
        compat.shard_map(lambda x: x, _mesh_1d(), in_specs=(P(),),
                         out_specs=P(), auto=frozenset({"data"}))


# ------------------------------------------------------------- compile cache

def test_compile_cache_dir_env_wins(monkeypatch):
    from repro.common import compile_cache
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: calls.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert compile_cache.use_compile_cache() == "/elsewhere/cache"
    assert calls == []            # JAX reads the variable itself
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    got = compile_cache.use_compile_cache()
    assert got == compile_cache.DEFAULT_DIR
    assert calls == [("jax_compilation_cache_dir", got)]
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert got == os.path.join(root, ".jax_cache")


# --------------------------------------------- checkpoint bf16 regression

def test_save_load_bf16_roundtrip_via_shim(tmp_path):
    """save_pytree/load_pytree flatten through the compat shim; bf16
    leaves must round-trip bit-exactly (they ride as uint16 views)."""
    tree = {"w": (jnp.arange(6, dtype=jnp.float32) / 3.0)
                 .astype(jnp.bfloat16).reshape(2, 3),
            "nested": [{"b": jnp.asarray([1.5, -2.25], jnp.bfloat16)}],
            "f32": jnp.linspace(0, 1, 5)}
    path = str(tmp_path / "bf16.npz")
    save_pytree(path, tree)
    out = load_pytree(path, jax.tree.map(jnp.zeros_like, tree))
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(out)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
