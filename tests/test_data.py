"""Data pipeline: determinism + the paper's per-replica sampling orders."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.data import DataPipeline, make_markov_lm_dataset, \
    make_prototype_image_dataset
from repro.data.pipeline import replica_batch_indices


def test_dataset_deterministic():
    a = make_markov_lm_dataset(vocab=32, seq_len=16, n_train=64, n_test=16,
                               seed=7)
    b = make_markov_lm_dataset(vocab=32, seq_len=16, n_train=64, n_test=16,
                               seed=7)
    np.testing.assert_array_equal(a.train_inputs, b.train_inputs)
    c = make_markov_lm_dataset(vocab=32, seq_len=16, n_train=64, n_test=16,
                               seed=8)
    assert not np.array_equal(np.asarray(a.train_inputs),
                              np.asarray(c.train_inputs))


def test_markov_structure_learnable():
    """Next-token distribution is non-uniform (there is structure)."""
    ds = make_markov_lm_dataset(vocab=16, seq_len=64, n_train=256,
                                n_test=64, seed=0, concentration=0.1)
    x = np.asarray(ds.train_inputs)
    y = np.asarray(ds.train_targets)
    # empirical transition matrix should be concentrated
    counts = np.zeros((16, 16))
    np.add.at(counts, (x.reshape(-1), y.reshape(-1)), 1)
    probs = counts / np.maximum(counts.sum(1, keepdims=True), 1)
    top1 = probs.max(axis=1)
    assert top1.mean() > 0.3      # uniform would be 1/16


def test_replica_sampling_orders_differ():
    """Paper Alg. 1 line 6: each replica sees its own batch order."""
    key = jax.random.key(0)
    i0 = replica_batch_indices(key, 0, step=3, n_train=256, batch_size=16)
    i1 = replica_batch_indices(key, 1, step=3, n_train=256, batch_size=16)
    assert not np.array_equal(np.asarray(i0), np.asarray(i1))


def test_epoch_is_without_replacement():
    key = jax.random.key(0)
    n, bs = 128, 16
    seen = []
    for step in range(n // bs):
        seen.append(np.asarray(
            replica_batch_indices(key, 0, step, n, bs)))
    allidx = np.concatenate(seen)
    assert sorted(allidx.tolist()) == list(range(n))


def test_stacked_batch_shapes():
    ds = make_markov_lm_dataset(vocab=32, seq_len=16, n_train=64, n_test=16)
    pipe = DataPipeline(ds, batch_size=8, n_replicas=3)
    xb, yb = pipe.stacked_batch(0)
    assert xb.shape == (3, 8, 16) and yb.shape == (3, 8, 16)


def test_image_dataset_label_noise_and_shapes():
    ds = make_prototype_image_dataset(n_classes=4, image_size=8,
                                      n_train=64, n_test=32,
                                      label_noise=0.2, seed=0)
    assert ds.train_inputs.shape == (64, 8, 8, 3)
    assert int(ds.train_targets.max()) < 4


def test_markov_large_vocab_rows_are_sparse():
    """Above MARKOV_FANOUT tokens the chain draws each row's successors
    from a fixed support, so it builds in O(vocab) memory."""
    from repro.data.synthetic import MARKOV_FANOUT
    vocab = 4 * MARKOV_FANOUT
    ds = make_markov_lm_dataset(vocab=vocab, seq_len=64, n_train=256,
                                n_test=16, seed=0)
    x = np.asarray(ds.train_inputs).reshape(-1)
    y = np.asarray(ds.train_targets).reshape(-1)
    assert 0 <= x.min() and x.max() < vocab
    succ = {}
    for a, b in zip(x, y):
        succ.setdefault(int(a), set()).add(int(b))
    assert max(len(s) for s in succ.values()) <= MARKOV_FANOUT
