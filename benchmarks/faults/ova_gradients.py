"""Planted fault ``ova_gradients``: from the planted iteration on each class's
gradient and hessian come from a sigmoid of its own score, as one-vs-all's
would (``sigma(s_k) - [y = k]``, ``sigma(s_k) (1 - sigma(s_k))``), and not from
the softmax over the classes. Every tree is a sound tree of the gradients it
was handed and moves its own class; only their law gives it away.

What a planted fault is, and what ``iteration`` says: ``state_unchanged.py``.
"""
from __future__ import annotations

import contextlib


@contextlib.contextmanager
def planted(iteration: int = 1):
    import jax

    from lightgbm_tpu.models.gbdt import GBDT

    gradients = GBDT._compute_gradients

    def gradients_one_vs_all(self, init_scores):
        if self.iter_ < iteration:
            return gradients(self, init_scores)
        p = jax.nn.sigmoid(self.scores)
        return p - self.objective._onehot_dev, p * (1.0 - p)

    try:
        GBDT._compute_gradients = gradients_one_vs_all
        yield
    finally:
        GBDT._compute_gradients = gradients
