"""Packed WA state (repro.common.packing): round-trip, single-launch
guarantees, exact (0 ULP) equivalence vs the per-leaf formulation, and
checkpoint round-trip + migration."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common.packing import (ALIGN, pack, pack_spec, pack_stacked,
                                  unpack, unpack_leaf)
from repro.core import (HWAConfig, HWAState, hwa_init, hwa_sync,
                        online_average, window_init, window_update)
from repro.kernels import ref as kref
from repro.launch.hlo import count_pallas_calls
from repro.optim import sgd


def ragged_tree(seed=0):
    """Ragged shapes, mixed dtypes, an empty leaf, a scalar."""
    ks = jax.random.split(jax.random.key(seed), 4)
    return {"w": jax.random.normal(ks[0], (37, 13)),
            "blocks": [{"m": jax.random.normal(ks[1], (8, 128)),
                        "b": jax.random.normal(ks[2], (128,)).astype(
                            jnp.bfloat16)}],
            "empty": jnp.zeros((0, 5)),
            "scale": jax.random.normal(ks[3], ()).astype(jnp.float16)}


def params_like(seed=0):
    k1, k2 = jax.random.split(jax.random.key(seed))
    return {"w": jax.random.normal(k1, (4, 3)),
            "b": jax.random.normal(k2, (7,))}


# ------------------------------------------------------------- round-trip


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_pack_unpack_roundtrip(seed):
    tree = ragged_tree(seed)
    spec = pack_spec(tree)
    assert spec.padded % ALIGN == 0 and spec.padded >= spec.size
    buf = pack(tree, spec)
    assert buf.shape == (spec.padded,) and buf.dtype == jnp.float32
    back = unpack(buf, spec)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


@pytest.mark.slow
def test_roundtrip_property():
    """Hypothesis sweep over arbitrary pytrees (shapes incl. empty/scalar,
    float dtypes that embed exactly in the f32 buffer)."""
    pytest.importorskip("hypothesis", reason="hypothesis not installed "
                        "(see requirements-dev.txt)")
    from hypothesis import given, settings, strategies as st

    shapes = st.lists(st.integers(0, 9), min_size=0, max_size=3).map(tuple)
    dtypes = st.sampled_from(["float32", "bfloat16", "float16"])

    @given(st.lists(st.tuples(shapes, dtypes), min_size=0, max_size=8),
           st.integers(0, 1000))
    @settings(max_examples=25, deadline=None)
    def run(leaf_specs, seed):
        ks = jax.random.split(jax.random.key(seed), max(len(leaf_specs), 1))
        tree = {f"l{i}": jax.random.normal(ks[i], shape).astype(dt)
                for i, (shape, dt) in enumerate(leaf_specs)}
        spec = pack_spec(tree)
        back = unpack(pack(tree, spec), spec)
        for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
            assert a.shape == b.shape and a.dtype == b.dtype
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))

    run()


def test_unpack_leaf_and_stacked_views():
    tree = ragged_tree()
    spec = pack_spec(tree)
    buf = pack(tree, spec)
    flat = jax.tree.leaves(tree)
    for i in range(spec.n_leaves):
        np.testing.assert_array_equal(
            np.asarray(unpack_leaf(buf, spec, i), np.float32),
            np.asarray(flat[i], np.float32))
    stacked_tree = jax.tree.map(lambda x: jnp.stack([x, 2 * x]), tree)
    sbuf = pack_stacked(stacked_tree, spec)
    assert sbuf.shape == (2, spec.padded)
    np.testing.assert_array_equal(np.asarray(sbuf[0]), np.asarray(buf))
    # unpack preserves leading batch dims (ring rows never get unpacked
    # wholesale in production; this is the debugging view)
    back = unpack(sbuf, spec)
    for a, b in zip(jax.tree.leaves(stacked_tree), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


# ------------------------------------------------- shard-aware layout
#
# The mesh-resident sync keeps the window state in a segment-major layout
# (one segment per device of the packed super-axis) so packing is a
# purely LOCAL operation on every device. These tests pin the invariants
# that make that work (pure layout math — no mesh needed).


def sharded_tree(seed=0):
    """Leaves covering all placement cases: dim-0 sharded, dim-1 sharded,
    replicated (indivisible), scalar."""
    ks = jax.random.split(jax.random.key(seed), 4)
    return {"embed": jax.random.normal(ks[0], (8, 10)),     # shard dim 0
            "head": jax.random.normal(ks[1], (10, 8)),      # shard dim 1
            "bias": jax.random.normal(ks[2], (7,)),         # replicated
            "scale": jax.random.normal(ks[3], ())}          # replicated


SHARD_DIMS = [None, 0, 1, None]       # flatten order: bias, embed, head, scale


@pytest.mark.parametrize("shards", [2, 4])
def test_sharded_layout_roundtrip(shards):
    tree = sharded_tree()
    spec = pack_spec(tree, align=16, shards=shards, shard_dims=SHARD_DIMS,
                     axes=("model",))
    assert spec.padded == shards * spec.seg_len
    assert spec.seg_len % spec.align == 0
    buf = pack(tree, spec)
    assert buf.shape == (spec.padded,)
    back = unpack(buf, spec)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    for i in range(spec.n_leaves):
        np.testing.assert_array_equal(
            np.asarray(unpack_leaf(buf, spec, i), np.float32),
            np.asarray(jax.tree.leaves(tree)[i], np.float32))
    stacked = jax.tree.map(lambda x: jnp.stack([x, 3 * x]), tree)
    sbuf = pack_stacked(stacked, spec)
    np.testing.assert_array_equal(np.asarray(sbuf[0]), np.asarray(buf))
    np.testing.assert_array_equal(np.asarray(sbuf[1]), 3 * np.asarray(buf))


def test_local_spec_segments_are_local_packs():
    """THE mesh-resident invariant: segment s of the global pack equals
    the local pack of shard s's leaf slices under spec.local_spec()."""
    shards = 2
    tree = sharded_tree()
    spec = pack_spec(tree, align=16, shards=shards, shard_dims=SHARD_DIMS,
                     axes=("model",))
    lspec = spec.local_spec()
    assert lspec.shards == 1 and lspec.padded == spec.seg_len
    buf = np.asarray(pack(tree, spec))
    flat, _ = jax.tree.flatten(tree)
    for s in range(shards):
        local_flat = []
        for leaf, ls in zip(flat, spec.leaves):
            if ls.shard_dim is None:
                local_flat.append(leaf)
            else:
                c = leaf.shape[ls.shard_dim] // shards
                local_flat.append(jax.lax.slice_in_dim(
                    leaf, s * c, (s + 1) * c, axis=ls.shard_dim))
        local_tree = jax.tree.unflatten(spec.treedef, local_flat)
        seg = np.asarray(pack(local_tree, lspec))
        np.testing.assert_array_equal(
            buf[s * spec.seg_len:(s + 1) * spec.seg_len], seg)


def test_sharded_layout_update_bitwise_equals_contiguous():
    """The same elementwise update on both layouts yields bit-identical
    leaf views (packing is layout-only)."""
    tree = sharded_tree()
    spec_c = pack_spec(tree, align=16)
    spec_s = pack_spec(tree, align=16, shards=2, shard_dims=SHARD_DIMS)
    new = sharded_tree(7)
    outs = {}
    for name, spec in [("contig", spec_c), ("sharded", spec_s)]:
        ring = jnp.zeros((3, spec.padded))
        total = pack(tree, spec)
        ring2, total2, avg = kref.wa_window_update_ref(
            ring, total, pack(new, spec), 1, 0.0, 0.5)
        outs[name] = (unpack(ring2[1], spec), unpack(total2, spec),
                      unpack(avg, spec))
    for a, b in zip(jax.tree.leaves(outs["contig"]),
                    jax.tree.leaves(outs["sharded"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_repack_and_spec_json_roundtrip():
    from repro.common.packing import repack, spec_from_json, spec_to_json
    tree = sharded_tree()
    spec_c = pack_spec(tree, align=16)
    spec_s = pack_spec(tree, align=16, shards=2, shard_dims=SHARD_DIMS,
                       axes=("data", "model"))
    buf = pack(tree, spec_s)
    np.testing.assert_array_equal(np.asarray(repack(buf, spec_s, spec_c)),
                                  np.asarray(pack(tree, spec_c)))
    # ring-style lead dims survive repack
    ring = jnp.stack([buf, 2 * buf])
    back = repack(ring, spec_s, spec_c)
    np.testing.assert_array_equal(np.asarray(back[1]),
                                  2 * np.asarray(pack(tree, spec_c)))
    rehydrated = spec_from_json(spec_to_json(spec_s))
    assert rehydrated.same_layout(spec_s)
    assert rehydrated.axes == ("data", "model")
    # treedef-less specs still drive leaf-level ops
    np.testing.assert_array_equal(
        np.asarray(unpack_leaf(buf, rehydrated, 1)),
        np.asarray(jax.tree.leaves(tree)[1]))


def test_pack_spec_rejects_indivisible_shard_dim():
    tree = sharded_tree()
    with pytest.raises(ValueError, match="cannot shard"):
        # bias is (7,): 7 % 4 != 0
        pack_spec(tree, shards=4, shard_dims=[0, None, None, None])


# ------------------------------------------------------ grouped layout
#
# Mixed (FSDP-style) tilings: leaves shard over DIFFERENT axis sets, some
# over several dims at once. No single super-axis aligns them, so the
# grouped layout gives each placement key its own contiguous range
# (PackGroup) — its own shard count and super-axis — and replicated
# leaves a shards==1 range stored once. Pure layout math, no mesh needed.

GROUPED_SIZES = {"data": 2, "model": 3}


def grouped_tree(seed=0):
    """One leaf per placement class: 2-dim data×model tile, data-only,
    model-only, replicated vector, replicated scalar."""
    ks = jax.random.split(jax.random.key(seed), 5)
    return {"fs": jax.random.normal(ks[0], (4, 6)),    # data × model
            "emb": jax.random.normal(ks[1], (8, 5)),   # dim 0 over data
            "head": jax.random.normal(ks[2], (5, 6)),  # dim 1 over model
            "bias": jax.random.normal(ks[3], (7,)),    # replicated
            "scale": jax.random.normal(ks[4], ())}     # replicated


# flatten order: bias, emb, fs, head, scale
GROUPED_PLACEMENTS = [
    (),
    ((0, ("data",)),),
    ((0, ("data",)), (1, ("model",))),
    ((1, ("model",)),),
    (),
]


def grouped_spec(tree, align=8):
    from repro.common.packing import pack_spec_grouped
    return pack_spec_grouped(tree, align=align,
                             placements=GROUPED_PLACEMENTS,
                             axis_sizes=GROUPED_SIZES)


@pytest.mark.parametrize("seed", [0, 3])
def test_grouped_layout_roundtrip(seed):
    tree = grouped_tree(seed)
    spec = grouped_spec(tree)
    gt = spec.group_table()
    assert spec.is_grouped and spec.n_groups == 4
    assert spec.padded == sum(g.padded for g in gt)
    assert all(g.seg_len % spec.align == 0 for g in gt)
    # group ranges are contiguous and ordered by first appearance
    assert [g.offset for g in gt] == \
        [sum(h.padded for h in gt[:i]) for i in range(len(gt))]
    buf = pack(tree, spec)
    assert buf.shape == (spec.padded,)
    back = unpack(buf, spec)
    flat = jax.tree.leaves(tree)
    for a, b in zip(flat, jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    for i in range(spec.n_leaves):
        np.testing.assert_array_equal(
            np.asarray(unpack_leaf(buf, spec, i), np.float32),
            np.asarray(flat[i], np.float32))
    stacked = jax.tree.map(lambda x: jnp.stack([x, 3 * x]), tree)
    sbuf = pack_stacked(stacked, spec)
    np.testing.assert_array_equal(np.asarray(sbuf[0]), np.asarray(buf))
    np.testing.assert_array_equal(np.asarray(sbuf[1]), 3 * np.asarray(buf))


def test_grouped_segments_are_local_packs():
    """THE mesh-resident invariant, grouped: for every device coordinate
    (c_data, c_model), packing the device's LOCAL leaf blocks under
    spec.local_spec() reproduces exactly its segment of every group of
    the global pack — multi-dim tiles included."""
    tree = grouped_tree()
    spec = grouped_spec(tree)
    lspec = spec.local_spec()
    gt = spec.group_table()
    assert lspec.is_grouped and all(g.shards == 1
                                    for g in lspec.group_table())
    assert lspec.padded == sum(g.seg_len for g in gt)
    buf = np.asarray(pack(tree, spec))
    flat, treedef = jax.tree.flatten(tree)
    b, e, f, h, s = flat            # bias, emb, fs, head, scale
    nd, nm = GROUPED_SIZES["data"], GROUPED_SIZES["model"]
    for cd in range(nd):
        for cm in range(nm):
            local = jax.tree.unflatten(treedef, [
                b,
                e[cd * (8 // nd):(cd + 1) * (8 // nd)],
                f[cd * (4 // nd):(cd + 1) * (4 // nd),
                  cm * (6 // nm):(cm + 1) * (6 // nm)],
                h[:, cm * (6 // nm):(cm + 1) * (6 // nm)],
                s])
            lbuf = np.asarray(pack(local, lspec))
            # segment index per group: row-major over the group's axes
            seg = {(): 0, ("data",): cd, ("model",): cm,
                   ("data", "model"): cd * nm + cm}
            want = np.concatenate([
                buf[g.offset + seg[g.axes] * g.seg_len:
                    g.offset + (seg[g.axes] + 1) * g.seg_len]
                for g in gt])
            np.testing.assert_array_equal(lbuf, want)


def test_grouped_layout_update_bitwise_equals_contiguous():
    """The same elementwise update on the grouped and contiguous layouts
    yields bit-identical leaf views (packing is layout-only)."""
    tree = grouped_tree()
    spec_c = pack_spec(tree, align=8)
    spec_g = grouped_spec(tree)
    new = grouped_tree(9)
    outs = {}
    for name, spec in [("contig", spec_c), ("grouped", spec_g)]:
        ring = jnp.zeros((3, spec.padded))
        total = pack(tree, spec)
        ring2, total2, avg = kref.wa_window_update_ref(
            ring, total, pack(new, spec), 1, 0.0, 0.5)
        outs[name] = (unpack(ring2[1], spec), unpack(total2, spec),
                      unpack(avg, spec))
    for a, b in zip(jax.tree.leaves(outs["contig"]),
                    jax.tree.leaves(outs["grouped"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_grouped_repack_json_and_split_roundtrip():
    from repro.common.packing import (merge_groups, repack, spec_from_json,
                                      spec_to_json, split_groups)
    tree = grouped_tree()
    spec_c = pack_spec(tree, align=8)
    spec_g = grouped_spec(tree)
    buf = pack(tree, spec_g)
    # grouped <-> contiguous, both directions, bit-exact
    np.testing.assert_array_equal(np.asarray(repack(buf, spec_g, spec_c)),
                                  np.asarray(pack(tree, spec_c)))
    np.testing.assert_array_equal(
        np.asarray(repack(pack(tree, spec_c), spec_c, spec_g)),
        np.asarray(buf))
    # grouped <-> single-super-axis shard-aware layout
    spec_s = pack_spec(tree, align=8, shards=2,
                       shard_dims=[None, 0, 0, None, None],
                       axes=("data",))
    np.testing.assert_array_equal(
        np.asarray(repack(repack(buf, spec_g, spec_s), spec_s, spec_c)),
        np.asarray(pack(tree, spec_c)))
    # ring-style lead dims survive
    ring = jnp.stack([buf, 2 * buf])
    np.testing.assert_array_equal(
        np.asarray(repack(ring, spec_g, spec_c)[1]),
        2 * np.asarray(pack(tree, spec_c)))
    # JSON round-trip keeps groups and multi-dim tiles
    re = spec_from_json(spec_to_json(spec_g))
    assert re.same_layout(spec_g)
    assert re.group_table() == spec_g.group_table()
    assert any(ls.tiles is not None for ls in re.leaves)
    np.testing.assert_array_equal(
        np.asarray(unpack_leaf(buf, re, 2)),
        np.asarray(jax.tree.leaves(tree)[2]))
    # per-group runtime views merge back bit-exactly
    parts = split_groups(buf, spec_g)
    assert len(parts) == spec_g.n_groups
    np.testing.assert_array_equal(np.asarray(merge_groups(parts, spec_g)),
                                  np.asarray(buf))


def test_grouped_window_buffers_match_contract():
    from repro.common.packing import window_buffers
    tree = grouped_tree()
    spec_g = grouped_spec(tree)
    ring, total = window_buffers(spec_g, 3)
    assert isinstance(ring, tuple) and len(ring) == spec_g.n_groups
    for r, t, g in zip(ring, total, spec_g.group_table()):
        assert r.shape == (3, g.padded) and t.shape == (g.padded,)
    spec_c = pack_spec(tree, align=8)
    ring_c, total_c = window_buffers(spec_c, 3)
    assert ring_c.shape == (3, spec_c.padded)
    assert total_c.shape == (spec_c.padded,)


def test_pack_spec_grouped_rejections():
    from repro.common.packing import pack_spec_grouped
    tree = grouped_tree()
    with pytest.raises(ValueError, match="cannot tile"):
        # bias is (7,): 7 % 2 != 0
        pack_spec_grouped(tree, placements=[((0, ("data",)),), (), (), (),
                                            ()],
                          axis_sizes=GROUPED_SIZES)
    with pytest.raises(ValueError, match="ascending"):
        pack_spec_grouped(
            tree,
            placements=[(), (), ((1, ("model",)), (0, ("data",))), (), ()],
            axis_sizes=GROUPED_SIZES)


def test_grouped_window_state_checkpoint_cross_layout(tmp_path):
    """A grouped (per-group tuple) window state saves to the canonical
    single-buffer form and loads bit-exactly into a contiguous template,
    and a contiguous save loads into a grouped (tuple-buffer) template —
    grouped↔single-axis↔per-leaf migrations all repack, never copy-cast.
    """
    from repro.checkpoint import load_window_state, save_window_state
    from repro.common.packing import repack, split_groups
    from repro.core.offline import WindowState

    p = grouped_tree()
    ws = window_init(p, 3)
    for t in range(4):
        ws, _ = window_update(ws, grouped_tree(20 + t))
    spec_g = grouped_spec(p, align=8)
    ring_g = split_groups(repack(ws.ring, ws.spec, spec_g), spec_g)
    total_g = split_groups(repack(ws.total, ws.spec, spec_g), spec_g)
    ws_g = WindowState(ring=ring_g, total=total_g, count=ws.count,
                       next_idx=ws.next_idx, window=ws.window,
                       kind=ws.kind, spec=spec_g)
    path = str(tmp_path / "ws_grouped.npz")
    save_window_state(path, ws_g)
    back = load_window_state(path, window_init(p, 3))
    np.testing.assert_array_equal(np.asarray(back.ring), np.asarray(ws.ring))
    np.testing.assert_array_equal(np.asarray(back.total),
                                  np.asarray(ws.total))
    assert int(back.count) == int(ws.count)
    # contiguous save -> grouped tuple template
    path_c = str(tmp_path / "ws_contig.npz")
    save_window_state(path_c, ws)
    back_g = load_window_state(path_c, ws_g)
    assert isinstance(back_g.ring, tuple)
    for a, b in zip(back_g.ring, ring_g):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(back_g.total, total_g):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # per-leaf (pre-packing) checkpoint -> grouped template
    from repro.checkpoint import save_pytree
    old_ring = {k: np.stack([np.asarray(unpack(ws.ring[r], ws.spec)[k])
                             for r in range(3)]) for k in p}
    old_total = {k: np.asarray(unpack(ws.total, ws.spec)[k]) for k in p}
    path_l = str(tmp_path / "ws_per_leaf.npz")
    save_pytree(path_l, {"ring": old_ring, "total": old_total,
                         "count": ws.count, "next_idx": ws.next_idx})
    back_l = load_window_state(path_l, ws_g)
    for a, b in zip(back_l.ring, ring_g):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------------ layout choosers


def _fake_mesh(shape: dict):
    import types
    return types.SimpleNamespace(shape=shape, axis_names=tuple(shape))


def test_mesh_resident_layout_rejects_zero_size_leaves():
    """Regression (hoisted guard): a ZERO-SIZE REPLICATED leaf used to
    slip through the chooser — the `all(d > 0)` check only ran for
    sharded leaves — and break the segment-major invariant downstream.
    Both choosers must refuse the whole tree."""
    from jax.sharding import PartitionSpec as P
    from repro.launch.sync.packed import (_grouped_resident_layout,
                                          _mesh_resident_layout)
    mesh = _fake_mesh({"data": 2, "model": 2})
    specs = [P("model"), P()]
    shapes = [(8,), (0, 5)]
    assert _mesh_resident_layout(mesh, specs, shapes) == (None, None)
    assert _grouped_resident_layout(mesh, specs, shapes) is None
    # control: dropping the zero-size leaf re-qualifies the same tree
    axes, dims = _mesh_resident_layout(mesh, specs[:1], shapes[:1])
    assert axes == ("model",) and dims == [0]
    # the degenerate fully-replicated (shards==1) layout stays available
    # — contiguous packing supports empty leaves, only SHARDED segment
    # layouts must refuse them
    axes, dims = _mesh_resident_layout(mesh, [P(), P()], [(4,), (0, 5)])
    assert axes == () and dims == [None, None]


def test_grouped_resident_layout_placements():
    from jax.sharding import PartitionSpec as P
    from repro.launch.sync.packed import _grouped_resident_layout
    mesh = _fake_mesh({"replica": 2, "data": 2, "model": 2})
    specs = [P("data"), P(None, "model"), P("data", "model"), P()]
    shapes = [(4,), (3, 6), (4, 6), (5,)]
    pl = _grouped_resident_layout(mesh, specs, shapes,
                                  exclude=("replica",))
    assert pl == (((0, ("data",)),), ((1, ("model",)),),
                  ((0, ("data",)), (1, ("model",))), ())
    # a leaf sharded over an excluded (replica) axis disqualifies
    assert _grouped_resident_layout(mesh, [P("replica")], [(4,)],
                                    exclude=("replica",)) is None
    # an indivisible tiled dim disqualifies
    assert _grouped_resident_layout(mesh, [P("model")], [(7,)]) is None
    # fully-replicated trees are the single-axis chooser's job
    assert _grouped_resident_layout(mesh, [P()], [(4,)]) is None


def test_choose_resident_spec_prefers_single_axis():
    """Uniform tilings keep the PR-3 single-super-axis layout (bit- and
    layout-compatible with existing checkpoints); only genuinely mixed
    tilings get the grouped one."""
    from jax.sharding import PartitionSpec as P
    from repro.launch.sync.packed import choose_resident_spec
    mesh = _fake_mesh({"data": 2, "model": 2})
    abs_tree = {"a": jax.ShapeDtypeStruct((8, 4), jnp.float32),
                "b": jax.ShapeDtypeStruct((6,), jnp.float32)}
    uniform = choose_resident_spec(mesh, abs_tree,
                                   [P(None, "model"), P()],
                                   [(8, 4), (6,)])
    assert not uniform.is_grouped and uniform.axes == ("model",)
    mixed = choose_resident_spec(mesh, abs_tree,
                                 [P("data", "model"), P("model")],
                                 [(8, 4), (6,)])
    assert mixed.is_grouped and mixed.n_groups == 2


# ----------------------------------------- 0 ULP vs per-leaf formulation


@pytest.mark.parametrize("use_kernel", [False, True])
def test_window_update_bitwise_equals_per_leaf(use_kernel):
    """The packed window state is bit-identical (0 ULP, f32) to running
    the reference update independently on every leaf."""
    I = 3
    p0 = params_like()
    ws = window_init(p0, I)
    leaf_ring = jax.tree.map(lambda x: jnp.zeros((I,) + x.shape), p0)
    leaf_total = jax.tree.map(jnp.zeros_like, p0)
    is3 = lambda x: isinstance(x, tuple) and len(x) == 3
    for t in range(7):
        outer = params_like(100 + t)
        ws, wa = window_update(ws, outer, use_kernel=use_kernel)
        idx, full = t % I, float(t >= I)
        inv = 1.0 / min(t + 1, I)
        triples = jax.tree.map(
            lambda r, tt, n: kref.wa_window_update_ref(
                r, tt, n, idx, full, inv), leaf_ring, leaf_total, outer)
        leaf_ring = jax.tree.map(lambda x: x[0], triples, is_leaf=is3)
        leaf_total = jax.tree.map(lambda x: x[1], triples, is_leaf=is3)
        leaf_wa = jax.tree.map(lambda x: x[2], triples, is_leaf=is3)
        for a, b in zip(jax.tree.leaves(wa), jax.tree.leaves(leaf_wa)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        unpacked_total = unpack(ws.total, ws.spec)
        for a, b in zip(jax.tree.leaves(unpacked_total),
                        jax.tree.leaves(leaf_total)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        for row in range(I):
            ring_row = unpack(ws.ring[row], ws.spec)
            for a, b in zip(jax.tree.leaves(ring_row),
                            jax.tree.leaves(leaf_ring)):
                np.testing.assert_array_equal(np.asarray(a),
                                              np.asarray(b[row]))


def test_online_average_kernel_bitwise_equals_per_leaf():
    K = 4   # power of two: sum*(1/K) == sum/K bitwise
    stacked = jax.tree.map(
        lambda x: jnp.stack([x * (i + 1) for i in range(K)]),
        params_like())
    got = online_average(stacked, use_kernel=True)
    want = jax.tree.map(lambda x: jnp.mean(x, 0), stacked)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("window_stride", [1, 2])
def test_hwa_sync_kernel_path_equals_reference(window_stride):
    """Fused sync (stride 1: single launch) and the packed two-step
    (stride 2: cond'd) produce bitwise-identical state vs the jnp path
    for K=2 (1/K exact in f32)."""
    opt = sgd(momentum=0.0)
    mk = lambda uk: HWAConfig(n_replicas=2, window=3, use_kernels=uk,
                              window_stride=window_stride)
    states = {}
    for uk in (False, True):
        state = hwa_init(mk(uk), params_like(), opt)
        inner = jax.tree.map(
            lambda x: jnp.stack([x, x * 1.5]), params_like(1))
        state = HWAState(inner=inner, inner_opt=state.inner_opt,
                         window_state=state.window_state, wa=state.wa,
                         cycle=state.cycle, step=state.step)
        for _ in range(3):
            state, _ = hwa_sync(mk(uk), state)
        states[uk] = state
    a, b = states[False], states[True]
    for x, y in zip(jax.tree.leaves((a.inner, a.wa)),
                    jax.tree.leaves((b.inner, b.wa))):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    np.testing.assert_array_equal(np.asarray(a.window_state.total),
                                  np.asarray(b.window_state.total))
    np.testing.assert_array_equal(np.asarray(a.window_state.ring),
                                  np.asarray(b.window_state.ring))
    assert int(a.window_state.count) == int(b.window_state.count)


# --------------------------------------------------- one launch, always


def test_window_update_is_one_pallas_call():
    """O(1) launches regardless of leaf count (the tentpole guarantee)."""
    tree = {f"l{i}": jnp.ones((5 + i,)) for i in range(12)}
    ws = window_init(tree, 4)
    jaxpr = jax.make_jaxpr(
        lambda w, o: window_update(w, o, use_kernel=True))(ws, tree)
    assert count_pallas_calls(jaxpr) == 1


def test_online_average_is_one_pallas_call():
    tree = {f"l{i}": jnp.ones((3, 5 + i)) for i in range(12)}
    jaxpr = jax.make_jaxpr(
        lambda t: online_average(t, use_kernel=True))(tree)
    assert count_pallas_calls(jaxpr) == 1


def test_fused_sync_is_one_pallas_call_total():
    cfg = HWAConfig(n_replicas=2, window=3, use_kernels=True)
    state = hwa_init(cfg, {f"l{i}": jnp.ones((7 + i,)) for i in range(12)},
                     sgd(momentum=0.0))
    jaxpr = jax.make_jaxpr(lambda s: hwa_sync(cfg, s))(state)
    assert count_pallas_calls(jaxpr) == 1


def test_per_leaf_path_is_one_launch_per_leaf():
    """The baseline the packed path replaces: L leaves ⇒ L launches."""
    from repro.kernels import ops as kops
    tree = {f"l{i}": jnp.ones((5 + i,)) for i in range(12)}
    ring = jax.tree.map(lambda x: jnp.zeros((4,) + x.shape), tree)
    total = jax.tree.map(jnp.zeros_like, tree)
    jaxpr = jax.make_jaxpr(lambda r, t, n: jax.tree.map(
        lambda rr, tt, nn: kops.wa_window_update(rr, tt, nn, 0, 1.0, 0.25),
        r, t, n))(ring, total, tree)
    assert count_pallas_calls(jaxpr) == len(jax.tree.leaves(tree))


# ------------------------------------------------------------ checkpoint


def test_window_state_checkpoint_roundtrip(tmp_path):
    from repro.checkpoint import load_window_state, save_window_state
    ws = window_init(params_like(), 3)
    for t in range(4):
        ws, _ = window_update(ws, params_like(10 + t))
    path = str(tmp_path / "ws.npz")
    save_window_state(path, ws)
    like = window_init(params_like(), 3)
    back = load_window_state(path, like)
    np.testing.assert_array_equal(np.asarray(back.ring), np.asarray(ws.ring))
    np.testing.assert_array_equal(np.asarray(back.total),
                                  np.asarray(ws.total))
    assert int(back.count) == int(ws.count)
    assert int(back.next_idx) == int(ws.next_idx)
    assert back.spec == ws.spec


def test_window_state_migration_from_per_leaf(tmp_path):
    """Pre-packing checkpoints stored one ring/total leaf PER PARAMETER;
    loading re-packs them bit-identically."""
    from repro.checkpoint import load_window_state, save_pytree
    I = 3
    p = params_like()
    ws = window_init(p, I)
    for t in range(4):
        ws, _ = window_update(ws, params_like(10 + t))
    # write the OLD format: per-leaf (I, *shape) ring and (*shape) total
    old_ring = {k: np.stack([np.asarray(unpack(ws.ring[r], ws.spec)[k])
                             for r in range(I)]) for k in p}
    old_total = {k: np.asarray(unpack(ws.total, ws.spec)[k]) for k in p}
    path = str(tmp_path / "old_ws.npz")
    save_pytree(path, {"ring": old_ring, "total": old_total,
                       "count": ws.count, "next_idx": ws.next_idx})
    back = load_window_state(path, window_init(p, I))
    np.testing.assert_array_equal(np.asarray(back.ring), np.asarray(ws.ring))
    np.testing.assert_array_equal(np.asarray(back.total),
                                  np.asarray(ws.total))
    assert int(back.count) == int(ws.count)


def test_window_state_checkpoint_cross_layout(tmp_path):
    """A window state saved under a shard-aware (mesh) layout loads
    bit-exactly into a contiguous (single-device) template, and back —
    the save records the layout, the load repacks."""
    from repro.checkpoint import load_window_state, save_window_state
    from repro.core.offline import WindowState

    p = params_like()       # {"w": (4,3), "b": (7,)} — flatten: b, w
    ws = window_init(p, 3)
    for t in range(4):
        ws, _ = window_update(ws, params_like(10 + t))
    # re-express the same state in a 3-way sharded layout (w on dim 1)
    from repro.common.packing import repack
    spec_s = pack_spec(p, align=16, shards=3, shard_dims=[None, 1],
                       axes=("model",))
    ws_s = WindowState(ring=repack(ws.ring, ws.spec, spec_s),
                       total=repack(ws.total, ws.spec, spec_s),
                       count=ws.count, next_idx=ws.next_idx,
                       window=ws.window, kind=ws.kind, spec=spec_s)
    path = str(tmp_path / "ws_sharded.npz")
    save_window_state(path, ws_s)
    back = load_window_state(path, window_init(p, 3))
    np.testing.assert_array_equal(np.asarray(back.ring), np.asarray(ws.ring))
    np.testing.assert_array_equal(np.asarray(back.total),
                                  np.asarray(ws.total))
    assert int(back.count) == int(ws.count)
    # and the reverse direction: contiguous save -> sharded template
    path2 = str(tmp_path / "ws_contig.npz")
    save_window_state(path2, ws)
    like_s = WindowState(ring=jnp.zeros((3, spec_s.padded)),
                         total=jnp.zeros((spec_s.padded,)),
                         count=ws.count, next_idx=ws.next_idx,
                         window=ws.window, kind=ws.kind, spec=spec_s)
    back_s = load_window_state(path2, like_s)
    np.testing.assert_array_equal(np.asarray(back_s.ring),
                                  np.asarray(ws_s.ring))


def test_window_state_checkpoint_pre_metadata_into_sharded(tmp_path):
    """Checkpoints written BEFORE layout metadata existed (a single
    packed buffer, no spec_json) load into a shard-aware template: the
    only layout ever written back then was the default contiguous one,
    so the loader rederives it and repacks."""
    from repro.checkpoint import load_window_state, save_pytree
    from repro.common.packing import repack
    from repro.core.offline import WindowState

    p = params_like()
    ws = window_init(p, 3)
    for t in range(3):
        ws, _ = window_update(ws, params_like(20 + t))
    # simulate the old save: raw buffers only, no spec_json entry
    path = str(tmp_path / "old_packed.npz")
    save_pytree(path, {"ring": ws.ring, "total": ws.total,
                       "count": ws.count, "next_idx": ws.next_idx})
    spec_s = pack_spec(p, shards=3, shard_dims=[None, 1], axes=("model",))
    like_s = WindowState(ring=jnp.zeros((3, spec_s.padded)),
                         total=jnp.zeros((spec_s.padded,)),
                         count=ws.count, next_idx=ws.next_idx,
                         window=ws.window, kind=ws.kind, spec=spec_s)
    back = load_window_state(path, like_s)
    np.testing.assert_array_equal(np.asarray(back.ring),
                                  np.asarray(repack(ws.ring, ws.spec,
                                                    spec_s)))
    np.testing.assert_array_equal(np.asarray(back.total),
                                  np.asarray(repack(ws.total, ws.spec,
                                                    spec_s)))


def test_window_state_migration_rejects_mismatched_keys(tmp_path):
    """Same shapes under different key paths must NOT migrate silently —
    positional packing would put values at the wrong offsets."""
    from repro.checkpoint import load_window_state, save_pytree
    I = 2
    tmpl = {"a": jnp.zeros((3,)), "b": jnp.zeros((3,))}
    zeros = np.zeros((3,), np.float32)
    path = str(tmp_path / "bad_ws.npz")
    save_pytree(path, {
        "ring": {"c": np.zeros((I, 3), np.float32),
                 "d": np.zeros((I, 3), np.float32)},
        "total": {"c": zeros, "d": zeros},
        "count": jnp.zeros((), jnp.int32),
        "next_idx": jnp.zeros((), jnp.int32)})
    with pytest.raises(ValueError, match="key mismatch"):
        load_window_state(path, window_init(tmpl, I))


# ------------------------------------------------- serving publish path


def test_wa_snapshot_matches_window_mean(tmp_path):
    """The serving-tier snapshot (live state AND checkpoint file) is the
    bitwise packed W̿ for both window kinds."""
    from repro.checkpoint.io import load_wa_snapshot, save_window_state
    from repro.serve.publish import wa_snapshot
    for kind in ("ring", "streaming"):
        ws = window_init(params_like(), 3, kind=kind)
        want = None
        for t in range(2):
            ws, want = window_update(ws, params_like(10 + t))
        buf, spec = wa_snapshot(ws)
        np.testing.assert_array_equal(
            np.asarray(unpack(buf, spec, like=params_like())["w"]),
            np.asarray(want["w"]))
        path = str(tmp_path / f"ws_{kind}.npz")
        save_window_state(path, ws)
        buf2, spec2 = load_wa_snapshot(path)
        assert spec2.same_layout(spec)
        np.testing.assert_array_equal(np.asarray(buf2), np.asarray(buf))


def test_weight_publisher_repack_is_bit_exact():
    """Publishing from a foreign (shard-aware) layout is a pure layout
    move: served params are bitwise the source tree, double-buffered."""
    from repro.serve.publish import WeightPublisher

    class FakeEngine:
        def __init__(self, params):
            self.params = params

        def set_params(self, new):
            self.params = new

    eng = FakeEngine(params_like(0))
    pub = WeightPublisher(engine=eng)
    src_tree = params_like(5)
    src_spec = pack_spec(src_tree, align=16, shards=3,
                         shard_dims=[None, 1], axes=("model",))
    old = eng.params
    new = pub.publish_packed(pack(src_tree, src_spec), src_spec)
    assert eng.params is new and pub._standby is old
    for a, b in zip(jax.tree.leaves(new), jax.tree.leaves(src_tree)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert pub.n_published == 1


# ----------------------------------------- compressed WA precision (PR 10)


def test_wa_tokens_roundtrip_and_reject():
    from repro.common import quant
    assert quant.wa_dtype("bf16") == jnp.bfloat16
    assert quant.wa_dtype(jnp.float8_e4m3fn) == jnp.float8_e4m3fn
    for tok in ("f32", "bf16", "fp8"):
        assert quant.wa_token(quant.wa_dtype(tok)) == tok
    assert not quant.is_compressed("f32")
    assert quant.is_compressed("fp8") and quant.needs_scales("fp8")
    assert quant.is_compressed("bf16") and not quant.needs_scales("bf16")
    with pytest.raises(ValueError, match="no WA precision token"):
        quant.wa_token(jnp.float16)
    with pytest.raises(ValueError, match="not a multiple"):
        quant.n_scale_blocks(quant.SCALE_BLOCK + 1)


def test_ulp_distance_ladder():
    from repro.common.quant import max_ulp, ulp_distance
    x = np.float32(1.5)
    assert max_ulp(x, x) == 0
    assert max_ulp(x, np.nextafter(x, np.float32(2.0))) == 1
    # across the sign: the ladder counts subnormal steps, ±0 coincide
    denorm = np.nextafter(np.float32(0.0), np.float32(1.0))
    assert int(ulp_distance(np.float32(-0.0), np.float32(0.0))) == 0
    assert max_ulp(-denorm, denorm) == 2
    # mixed dtypes measure on the NARROWER ladder: two f32 values one
    # bf16 step apart are 1 apart, values rounding together are 0 apart
    a = jnp.float32(1.0)
    b = a + jnp.float32(jnp.finfo(jnp.bfloat16).eps)
    assert max_ulp(a.astype(jnp.bfloat16), b) == 1
    assert max_ulp(a.astype(jnp.bfloat16), a + jnp.float32(1e-6)) == 0
    # NaN is astronomically far from everything (budget = failure)
    assert max_ulp(np.float32(np.nan), np.float32(1.0)) > 2**30


def test_rel_ulp_error_floor_semantics():
    from repro.common.quant import rel_ulp_error
    ref = np.linspace(-2.0, 2.0, 64, dtype=np.float32)
    assert rel_ulp_error(ref, ref, "bf16") == 0.0
    # one bf16 quantization step at the working scale reads as ~1
    got = np.asarray(jnp.asarray(ref).astype(jnp.bfloat16), np.float32)
    assert 0.0 < rel_ulp_error(ref, got, "bf16") <= 1.0
    # near-zero entries do NOT blow up: the RMS floor pins the scale
    # (raw near-zero ULP distance would be in the thousands)
    ref2 = np.array([0.0, 1.0, -1.0, 0.5], np.float32)
    got2 = ref2 + np.float32(1e-4)
    assert rel_ulp_error(ref2, got2, "bf16") < 0.1


def test_kahan_add_zero_comp_is_plain_add():
    from repro.common.quant import kahan_add
    rng = np.random.default_rng(0)
    total = jnp.asarray(rng.standard_normal(256), jnp.float32)
    delta = jnp.asarray(rng.standard_normal(256), jnp.float32)
    t, _ = kahan_add(total, jnp.zeros_like(total), delta)
    np.testing.assert_array_equal(np.asarray(t), np.asarray(total + delta))


def test_kahan_add_beats_plain_f32_accumulation():
    from repro.common.quant import kahan_add
    # classic pathological sum: many increments far below the total's ULP
    n, big, small = 10_000, np.float32(1e6), np.float32(0.01)
    t = c = jnp.float32(0.0)
    plain = jnp.float32(0.0)
    t, c = kahan_add(t, c, jnp.float32(big))
    plain = plain + big
    for _ in range(n):
        t, c = kahan_add(t, c, jnp.float32(small))
        plain = plain + small
    exact = float(big) + n * float(small)
    assert abs(float(t) - exact) < abs(float(plain) - exact)
    assert abs(float(t) - exact) <= 1.0


def test_fp8_block_codec_roundtrip_and_edges():
    from repro.common import quant
    rng = np.random.default_rng(1)
    block = 16
    x = jnp.asarray(rng.standard_normal((4, 4 * block)) *
                    10.0 ** rng.integers(-3, 4, (4, 4 * block)), jnp.float32)
    s = quant.block_scales(x, block)
    assert s.shape == (4, 4) and s.dtype == jnp.float32
    q = quant.quantize_fp8(x, s, block)
    assert q.dtype == jnp.float8_e4m3fn
    back = quant.dequantize_fp8(q, s, block)
    assert bool(jnp.all(jnp.isfinite(back)))
    # e4m3 has a 3-bit mantissa: relative error ≤ 2^-4 of the block amax
    amax = np.repeat(np.asarray(s) * quant.FP8_MAX, block, axis=-1)
    assert np.max(np.abs(np.asarray(back) - np.asarray(x))) <= \
        np.max(amax) * 2.0 ** -4
    # signs survive wherever the value didn't underflow the block scale
    nz = np.asarray(back) != 0
    assert np.all(np.sign(np.asarray(back))[nz]
                  == np.sign(np.asarray(x))[nz])
    # all-zero block: scale 1.0, exact-zero round trip (no 0/0)
    z = jnp.zeros((2 * block,), jnp.float32)
    sz = quant.block_scales(z, block)
    np.testing.assert_array_equal(np.asarray(sz), np.ones(2, np.float32))
    np.testing.assert_array_equal(
        np.asarray(quant.dequantize_fp8(quant.quantize_fp8(z, sz, block),
                                        sz, block)), np.asarray(z))
    # a subnormal-scale block quantizes without NaN/inf
    tiny = jnp.full((block,), np.float32(1e-40))
    st = quant.block_scales(tiny, block)
    assert bool(jnp.all(jnp.isfinite(
        quant.dequantize_fp8(quant.quantize_fp8(tiny, st, block), st,
                             block))))


def test_encode_decode_slot_tokens():
    from repro.common.quant import decode_slot, encode_slot
    rng = np.random.default_rng(2)
    block = 32
    x = jnp.asarray(rng.standard_normal(2 * block), jnp.float32)
    # f32: bit-exact identity, no scales
    slot, s = encode_slot(x, "f32", block)
    assert s is None
    np.testing.assert_array_equal(np.asarray(decode_slot(slot)),
                                  np.asarray(x))
    # bf16: the cast, no scales
    slot, s = encode_slot(x, "bf16", block)
    assert s is None and slot.dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(decode_slot(slot)),
        np.asarray(x.astype(jnp.bfloat16).astype(jnp.float32)))
    # fp8: block-scaled, decode needs the scales
    slot, s = encode_slot(x, "fp8", block)
    assert slot.dtype == jnp.float8_e4m3fn and s.shape == (2,)
    back = decode_slot(slot, s, block)
    assert float(jnp.max(jnp.abs(back - x))) < float(jnp.max(jnp.abs(x)))


def test_window_aux_buffers_shapes():
    from repro.common.packing import window_aux_buffers, window_buffers
    from repro.common.quant import wa_dtype
    spec = pack_spec(params_like())                 # padded == ALIGN
    I = 3
    assert window_aux_buffers(spec, I, "f32") == (None, None)
    scales, comp = window_aux_buffers(spec, I, "bf16")
    assert scales is None and comp.shape == (spec.padded,) \
        and comp.dtype == jnp.float32
    scales, comp = window_aux_buffers(spec, I, "fp8")
    assert scales.shape == (I, spec.scale_blocks) \
        and bool(jnp.all(scales == 1.0))            # scale of a zero block
    ring, total = window_buffers(spec, I, wa_dtype("fp8"))
    assert ring.dtype == jnp.float8_e4m3fn and total.dtype == jnp.float32
    # grouped layouts get per-group tuples
    gspec = grouped_spec(grouped_tree(), align=8)
    gscales, gcomp = window_aux_buffers(gspec, I, "bf16")
    assert gscales is None and isinstance(gcomp, tuple) \
        and len(gcomp) == gspec.n_groups


def test_pack_spec_ring_dtype_json_and_layout_neutrality():
    from repro.common.packing import spec_from_json, spec_to_json
    spec = pack_spec(params_like())
    assert spec.ring_dtype == "float32"
    assert "ring_dtype" not in spec_to_json(spec)   # omitted == f32:
    # pre-compression checkpoints rehydrate unchanged
    for tok, name in (("bf16", "bfloat16"), ("fp8", "float8_e4m3fn")):
        sp = spec.with_ring_dtype(tok)
        assert sp.ring_dtype == name
        back = spec_from_json(spec_to_json(sp))
        assert back.ring_dtype == name
        assert sp.same_layout(spec) and spec.same_layout(sp)
    assert spec.with_ring_dtype("f32") is spec


@pytest.mark.parametrize("tok", ["bf16", "fp8"])
def test_compressed_window_update_matches_decoded_accounting(tok):
    """The compressed ring stores encode(mean); total/W̿ account for the
    DECODED values (what the ring can reproduce), Kahan-compensated, so
    W̿ == mean(decoded slots) to f32 round-off — and the f32 path stays
    exactly the pre-compression arithmetic (checked elsewhere
    bit-for-bit)."""
    from repro.common.quant import decode_slot
    from repro.core.offline import window_average_packed
    p = params_like()
    I = 3
    ws = window_init(p, I, ring_dtype=tok)
    assert ws.comp is not None and (ws.scales is None) == (tok == "bf16")
    for t in range(4):
        ws, wa = window_update(ws, params_like(10 + t))
    dec = decode_slot(ws.ring, ws.scales)
    want = np.mean(np.asarray(dec), axis=0)
    got = np.asarray(window_average_packed(ws))
    np.testing.assert_allclose(got, want, atol=1e-6, rtol=1e-6)


def test_compressed_window_update_kernel_matches_ref():
    """bf16 rings have a fused Pallas kernel (`wa_window_update_packed_c`)
    — it must agree with the jnp reference bit-for-bit."""
    p = params_like()
    ws_k = window_init(p, 3, ring_dtype="bf16")
    ws_r = window_init(p, 3, ring_dtype="bf16")
    for t in range(4):
        ws_k, wa_k = window_update(ws_k, params_like(20 + t),
                                   use_kernel=True)
        ws_r, wa_r = window_update(ws_r, params_like(20 + t),
                                   use_kernel=False)
        for a, b in zip(jax.tree.leaves(wa_k), jax.tree.leaves(wa_r)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(ws_k.ring),
                                  np.asarray(ws_r.ring))
    np.testing.assert_array_equal(np.asarray(ws_k.total),
                                  np.asarray(ws_r.total))
    np.testing.assert_array_equal(np.asarray(ws_k.comp),
                                  np.asarray(ws_r.comp))


@pytest.mark.parametrize("tok", ["bf16", "fp8"])
def test_compressed_window_state_checkpoint_bit_exact(tok):
    """Same-precision save/load round-trips the compressed ring (and its
    scales/comp companions) BIT-exactly — via integer views, a narrow
    float never round-trips through f32."""
    import tempfile

    from repro.checkpoint import load_window_state, save_window_state
    p = params_like()
    ws = window_init(p, 3, ring_dtype=tok)
    for t in range(4):
        ws, _ = window_update(ws, params_like(30 + t))
    with tempfile.TemporaryDirectory() as d:
        path = d + "/ws.npz"
        save_window_state(path, ws)
        back = load_window_state(path, window_init(p, 3, ring_dtype=tok))
    assert back.ring.dtype == ws.ring.dtype
    np.testing.assert_array_equal(
        np.asarray(back.ring.view(jnp.uint8)),
        np.asarray(ws.ring.view(jnp.uint8)))
    np.testing.assert_array_equal(np.asarray(back.total),
                                  np.asarray(ws.total))
    np.testing.assert_array_equal(np.asarray(back.comp),
                                  np.asarray(ws.comp))
    if tok == "fp8":
        np.testing.assert_array_equal(np.asarray(back.scales),
                                      np.asarray(ws.scales))


@pytest.mark.parametrize("src,dst", [("f32", "bf16"), ("f32", "fp8"),
                                     ("bf16", "f32"), ("fp8", "f32"),
                                     ("bf16", "fp8")])
def test_window_state_precision_migration(src, dst, tmp_path):
    """Loading a checkpoint into a template of a DIFFERENT ring precision
    re-encodes: ring = encode(decode(stored)), total = Σ decoded slots,
    comp reset (the compensation tracks a total that no longer exists)."""
    from repro.checkpoint import load_window_state, save_window_state
    from repro.common.quant import decode_slot, encode_slot, wa_dtype
    p = params_like()
    ws = window_init(p, 3, ring_dtype=src)
    for t in range(4):
        ws, _ = window_update(ws, params_like(40 + t))
    path = str(tmp_path / "ws.npz")
    save_window_state(path, ws)
    back = load_window_state(path, window_init(p, 3, ring_dtype=dst))
    assert back.ring.dtype == wa_dtype(dst)
    f32_ring = decode_slot(ws.ring, ws.scales)
    want_ring, want_scales = encode_slot(f32_ring, dst)
    np.testing.assert_array_equal(
        np.asarray(back.ring, np.float32),
        np.asarray(want_ring, np.float32))
    if want_scales is not None:
        np.testing.assert_array_equal(np.asarray(back.scales),
                                      np.asarray(want_scales))
    np.testing.assert_array_equal(
        np.asarray(back.total),
        np.asarray(jnp.sum(decode_slot(want_ring, want_scales), axis=0)))
    if dst == "f32":
        assert back.comp is None and back.scales is None
    else:
        np.testing.assert_array_equal(np.asarray(back.comp),
                                      np.zeros_like(np.asarray(back.total)))
    assert int(back.count) == int(ws.count)


def test_window_state_migration_into_grouped_compressed_raises(tmp_path):
    from repro.checkpoint import load_window_state, save_window_state
    from repro.common.packing import window_aux_buffers, window_buffers
    from repro.core.offline import WindowState
    p = params_like()
    ws = window_init(p, 3)
    for t in range(2):
        ws, _ = window_update(ws, params_like(50 + t))
    path = str(tmp_path / "ws.npz")
    save_window_state(path, ws)
    gtree = grouped_tree()
    gspec = grouped_spec(gtree, align=8).with_ring_dtype("bf16")
    ring, total = window_buffers(gspec, 3, jnp.bfloat16)
    _, comp = window_aux_buffers(gspec, 3, "bf16")
    like = WindowState(ring=ring, total=total,
                       count=jnp.zeros((), jnp.int32),
                       next_idx=jnp.zeros((), jnp.int32),
                       window=3, kind="ring", spec=gspec, comp=comp)
    with pytest.raises(ValueError):
        load_window_state(path, like)
