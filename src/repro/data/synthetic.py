"""Deterministic synthetic-but-learnable datasets.

The container is offline (no CIFAR/ImageNet), so the paper's claims are
validated on tasks with a real train/test generalization gap:

- ``make_markov_lm_dataset``: sequences from a fixed random 1st-order
  Markov chain over the vocabulary. A model must learn the transition
  structure; a finite train set can be memorized, fresh test sequences
  cannot — so test loss measures generalization exactly as the paper's
  test accuracy does.
- ``make_prototype_image_dataset``: Gaussian class prototypes in pixel
  space + per-sample noise + a fraction of label noise ("hard samples",
  §IV-C's memorization discussion). Used by the paper-faithful
  ResNet+BN+SGD pipeline.

Everything is generated from explicit PRNG keys — fully reproducible.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass
class SyntheticDataset:
    """A finite train split plus a held-out test split."""
    train_inputs: jax.Array
    train_targets: jax.Array
    test_inputs: jax.Array
    test_targets: jax.Array
    kind: str = "lm"  # "lm" | "image"

    @property
    def n_train(self) -> int:
        return int(self.train_inputs.shape[0])

    @property
    def n_test(self) -> int:
        return int(self.test_inputs.shape[0])


def _sample_markov(key, support, weights, n_seq: int, seq_len: int
                   ) -> jax.Array:
    """Sample ``n_seq`` sequences from a 1st-order chain whose row ``v``
    moves to ``support[v, j]`` with probability ``weights[v, j]``."""
    vocab = support.shape[0]
    k0, k1 = jax.random.split(key)
    first = jax.random.randint(k0, (n_seq,), 0, vocab)
    logits = jnp.log(weights + 1e-9)

    def step(prev, k):
        nxt = support[prev, jax.random.categorical(k, logits[prev])]
        return nxt, nxt

    keys = jax.random.split(k1, seq_len - 1)
    _, rest = jax.lax.scan(step, first, keys)
    return jnp.concatenate([first[None], rest], axis=0).T  # (n_seq, seq_len)


#: successors per token. A vocabulary up to this size gets a dense
#: transition matrix; a larger one draws each row's support at random, so
#: the chain costs O(vocab · MARKOV_FANOUT) memory (a dense (vocab, vocab)
#: matrix at vocab 49,155 would be 9.7 GB of f32).
MARKOV_FANOUT = 64


def make_markov_lm_dataset(vocab: int = 256, seq_len: int = 128,
                           n_train: int = 2048, n_test: int = 512,
                           seed: int = 0, concentration: float = 0.3
                           ) -> SyntheticDataset:
    """LM dataset: inputs are tokens, targets are next tokens."""
    key = jax.random.key(seed)
    kt, ktr, kte = jax.random.split(key, 3)
    # Sparse-ish random transition rows: low concentration -> low entropy
    # -> learnable structure with an achievable-but-nonzero loss floor.
    fanout = min(vocab, MARKOV_FANOUT)
    alpha = jnp.full((fanout,), concentration)
    weights = jax.random.dirichlet(kt, alpha, shape=(vocab,))
    if fanout == vocab:
        support = jnp.broadcast_to(jnp.arange(vocab), (vocab, vocab))
    else:
        support = jax.random.randint(jax.random.fold_in(kt, 1),
                                     (vocab, fanout), 0, vocab)
    train = _sample_markov(ktr, support, weights, n_train, seq_len + 1)
    test = _sample_markov(kte, support, weights, n_test, seq_len + 1)
    return SyntheticDataset(
        train_inputs=train[:, :-1], train_targets=train[:, 1:],
        test_inputs=test[:, :-1], test_targets=test[:, 1:], kind="lm")


def make_prototype_image_dataset(n_classes: int = 10, image_size: int = 16,
                                 channels: int = 3, n_train: int = 4096,
                                 n_test: int = 1024, noise: float = 0.7,
                                 label_noise: float = 0.05, seed: int = 0
                                 ) -> SyntheticDataset:
    """Image classification with Gaussian class prototypes + label noise."""
    key = jax.random.key(seed)
    kp, ktr, kte, kl = jax.random.split(key, 4)
    shape = (image_size, image_size, channels)
    protos = jax.random.normal(kp, (n_classes,) + shape)

    def split(k, n):
        ky, kx = jax.random.split(k)
        y = jax.random.randint(ky, (n,), 0, n_classes)
        x = protos[y] + noise * jax.random.normal(kx, (n,) + shape)
        return x.astype(jnp.float32), y

    xtr, ytr = split(ktr, n_train)
    xte, yte = split(kte, n_test)
    if label_noise > 0:
        k1, k2 = jax.random.split(kl)
        flip = jax.random.bernoulli(k1, label_noise, (n_train,))
        rand_y = jax.random.randint(k2, (n_train,), 0, n_classes)
        ytr = jnp.where(flip, rand_y, ytr)  # "hard samples" to memorize
    return SyntheticDataset(train_inputs=xtr, train_targets=ytr,
                            test_inputs=xte, test_targets=yte, kind="image")
