"""Production meshes (functions, never module-level constants — importing
this module must not touch jax device state).

Target: TPU v5e. Single pod = 16×16 = 256 chips, axes (data, model);
multi-pod = 2 pods = 512 chips, axes (pod, data, model). For HWA the
replica axis is the pod axis at multi-pod scale, or carved out of the data
axis on a single pod (DESIGN.md §2).

Mesh construction goes through ``repro.common.compat.make_mesh`` (every
axis ``Auto``).
"""
from __future__ import annotations

from repro.common.compat import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_hwa_mesh(n_replicas: int = 2, *, multi_pod: bool = False):
    """Mesh with an explicit HWA replica axis.

    multi_pod: (pod=K, data, model) — one HWA replica per pod, inter-pod
    traffic only at synchronization (the paper's communication story).
    single pod: (replica=K, data=16/K, model=16).
    """
    if multi_pod:
        return make_mesh((n_replicas, 16, 16), ("replica", "data", "model"))
    assert 16 % n_replicas == 0, n_replicas
    return make_mesh((n_replicas, 16 // n_replicas, 16),
                     ("replica", "data", "model"))


def make_test_mesh(shape=(2, 2, 2), axes=("replica", "data", "model")):
    """Small mesh for CI-scale SPMD tests (requires forced host devices)."""
    return make_mesh(shape, axes)


def make_tree_test_mesh(shape=(2, 2, 2), axes=("pod", "replica", "model")):
    """Pod-carved test mesh for the two-level sync tree (8 forced host
    devices): K = 4 replicas as 2 pods × 2 members, with a real ``model``
    TP axis so the shard-aware packed layout is exercised under the tree.

    The replica population is split over TWO axes — ``pod`` (slow,
    expensive cross-pod links) and ``replica`` (fast, pod-internal) — so
    the tree's inner sync reduces over ``replica`` only and the rare
    outer sync adds the one cross-``pod`` all-reduce
    (``launch.sync.topology.TwoLevel``). Axis order is pod-major: the
    stacked K dim sharded over ``("pod", "replica")`` keeps each pod a
    CONTIGUOUS replica block, which the 0-ULP flat↔tree parity relies on
    (docs/ARCHITECTURE.md §4). At production scale the driver carves the
    same (pod, replica, data, model) shape from the real topology
    (``repro.launch.train --sync-tree two-level``).
    """
    return make_mesh(shape, axes)
