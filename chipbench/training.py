"""Helpers the training drivers share: the program's model config for a
configuration document, the check that the seeded weights have the
program's layout, and the sampled points of the sync comparisons."""
from __future__ import annotations

import numpy as np

from chipbench.reference import granite

#: elements sampled from every leaf for the sync comparisons
SYNC_SAMPLE = 1 << 15


def model_config(doc: dict):
    """The program's ``ModelConfig`` for a configuration document."""
    from repro.models.types import ModelConfig
    d = granite.Dims.from_config(doc)
    prog = doc["program"]
    return ModelConfig(
        name=doc["name"], family=prog["family"], n_layers=d.n_layers,
        d_model=d.d_model, n_heads=d.n_heads, n_kv_heads=d.n_kv_heads,
        d_ff=d.d_ff, vocab_size=d.vocab, head_dim=d.head_dim,
        rope_theta=d.rope_theta, dtype=d.dtype, attn_impl=prog["attn_impl"],
        remat=prog["remat"])


def check_layout(lm, init, key):
    """The seeded weights must have the program's parameter layout."""
    import jax
    want = jax.tree.map(lambda s: (s.shape, str(s.dtype)), lm.abstract()[0])
    got = jax.tree.map(lambda s: (s.shape, str(s.dtype)),
                       jax.eval_shape(init, key))
    if want != got:
        raise ValueError(f"seeded weights do not match the program's "
                         f"parameter layout:\n{got}\n!=\n{want}")


def sample_points(key, params_abs, n: int):
    """Per leaf, sorted flat indices to compare (all when the leaf is
    small), and each leaf's offset in the packed layout: leaves back to
    back in flatten order."""
    import jax
    idx, offsets, off = [], [], 0
    leaves = jax.tree.leaves(params_abs)
    for i, leaf in enumerate(leaves):
        size = int(np.prod(leaf.shape))
        if size <= n:
            pick = np.arange(size)
        else:
            rng = np.random.default_rng(
                np.asarray(jax.random.key_data(jax.random.fold_in(key, i))))
            pick = np.sort(rng.choice(size, n, replace=False))
        idx.append(pick.astype(np.int32))
        offsets.append(off)
        off += size
    return idx, offsets


def gather_leaves(leaves, idx, lead: int):
    """Flat samples of each leaf (``lead`` leading axes kept)."""
    import jax.numpy as jnp
    return [jnp.take(x.reshape(x.shape[:lead] + (-1,)), i, axis=lead)
            for x, i in zip(leaves, idx)]


def leaf_names(dims) -> list[str]:
    """The parameter leaves' paths, in flatten order."""
    import jax
    tree = jax.eval_shape(lambda: granite.init_params(dims,
                                                      jax.random.key(0)))
    return [jax.tree_util.keystr(p)
            for p, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]
