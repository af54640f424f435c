"""Seeded click-log pool: the benchmark's traffic generator.

A Criteo-like log as ``repro.data.synthetic.ClickLogDataset`` makes it: 13
dense features from a standard normal, categorical ids whose ranks are
Zipf-distributed (``zipf_a``, 1.2 by default) and mapped to rows by a
per-table bijection so that the hot rows differ per table and spread over
the table, and labels from a hidden logistic teacher whose per-table effect
is the mean of the effects of the table's ids.  Two departures keep it
cheap at 34M rows: the per-table permutation is an affine bijection
``(a * rank + b) mod n`` with ``gcd(a, n) = 1`` instead of a stored
permutation, and the teacher's per-id effect is a hash of the id instead of a
stored vector.  Every seed gives the same sizes; only the draws differ.

Hotness (``multi_hot``, lookups per table and sample, the program's
``DLRMConfig.multi_hot``) gives ``sparse (S, F, h)``.  Slot 0 of every table
is the one-hot pool's draw, from the same stream; further slots come from a
second stream, so a one-hot pool is the same whatever the hotness.

``PoolDataset`` cycles the pool so that ``Emulator.run`` can train for any
number of steps, and tells the harness when the first evaluation batch is
asked for (the end of the measured window).
"""
from __future__ import annotations

import math

import numpy as np

VIRTUAL_TRAIN = 1 << 40      # "samples" the emulator sees: max_steps bounds it

_M64 = np.uint64(0xFFFFFFFFFFFFFFFF)


def _mix64(x):
    """splitmix64 finaliser over a uint64 array."""
    x = np.asarray(x, np.uint64)
    with np.errstate(over="ignore"):
        x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        x = x ^ (x >> np.uint64(31))
    return x & _M64


def affine_bijection(rng, n):
    """(a, b) with gcd(a, n) = 1: rank -> (a * rank + b) mod n is a bijection."""
    if n <= 1:
        return 1, 0
    a = int(rng.integers(1, n))
    while math.gcd(a, n) != 1:
        a = a % (n - 1) + 1
    return a, int(rng.integers(0, n))


def make_pool(table_sizes, num_dense, n_samples, seed, zipf_a=1.2,
              multi_hot=1):
    """-> dict(dense (S, num_dense) f32, sparse (S, F, h) i32, label (S,)
    f32)."""
    rng = np.random.default_rng([int(seed), 0x5EED])
    more = np.random.default_rng([int(seed), 0x5EED, 1])
    F = len(table_sizes)
    h = int(multi_hot)
    dense = rng.standard_normal((n_samples, num_dense), dtype=np.float32)
    sparse = np.empty((n_samples, F, h), np.int32)
    teacher_dense = rng.standard_normal(num_dense) / np.sqrt(num_dense)
    logits = dense.astype(np.float64) @ teacher_dense - 0.3
    salt = np.uint64(rng.integers(0, 1 << 62))
    for f, n in enumerate(table_sizes):
        ranks = np.minimum(rng.zipf(zipf_a, size=n_samples) - 1, n - 1)
        a, b = affine_bijection(rng, int(n))
        ranks = ranks[:, None]
        if h > 1:
            extra = np.minimum(
                more.zipf(zipf_a, size=(n_samples, h - 1)) - 1, n - 1)
            ranks = np.concatenate([ranks, extra], axis=1)
        ids = (ranks.astype(np.int64) * a + b) % int(n)
        sparse[:, f, :] = ids
        # per-id teacher effect: uniform with the variance of N(0, 0.49)
        x = _mix64(ids.astype(np.uint64) ^ (salt + np.uint64(f)))
        u = (x >> np.uint64(11)).astype(np.float64) * 2.0 ** -53
        logits += np.mean((2.0 * u - 1.0) * 0.7 * math.sqrt(3.0), axis=1)
    p = 1.0 / (1.0 + np.exp(-logits))
    label = (rng.uniform(size=n_samples) < p).astype(np.float32)
    return {"dense": dense, "sparse": sparse, "label": label}


class PoolDataset:
    """The ``ClickLogDataset`` interface that ``Emulator.run`` uses, over a
    pool of ``n_train`` batches (cycled) plus an evaluation block."""

    def __init__(self, pool, batch_size, n_train_batches, on_eval=None,
                 annotate=None):
        self.pool = pool
        self.batch_size = batch_size
        self.n_train_batches = n_train_batches
        self.n_eval = len(pool["label"]) - n_train_batches * batch_size
        self.on_eval = on_eval
        self.annotate = annotate

    def train_batch(self, i):
        j = (i % self.n_train_batches) * self.batch_size
        return {k: v[j:j + self.batch_size] for k, v in self.pool.items()}

    def eval_split(self, frac=0.1):
        return (0, VIRTUAL_TRAIN), (VIRTUAL_TRAIN, VIRTUAL_TRAIN + self.n_eval)

    def batches(self, batch_size, start=0, end=None, loop=False):
        if start >= VIRTUAL_TRAIN:
            yield from self._eval_batches(batch_size)
            return
        if batch_size != self.batch_size:
            raise ValueError(f"the pool holds batches of {self.batch_size}, "
                             f"not {batch_size}")
        i = 0
        while True:
            yield self._annotated("bench.batch", self.train_batch, i)
            i += 1

    def _annotated(self, name, fn, *args):
        if self.annotate is None:
            return fn(*args)
        with self.annotate(name):
            return fn(*args)

    def _eval_batches(self, batch_size):
        if self.on_eval is not None:
            self.on_eval()
        j0 = self.n_train_batches * self.batch_size
        for j in range(j0, j0 + self.n_eval, batch_size):
            yield self._annotated(
                "bench.eval", lambda j=j: {k: v[j:j + batch_size]
                                           for k, v in self.pool.items()})
