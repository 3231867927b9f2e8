"""Planted fault ``classes_in_sequence``: from the planted iteration on class
``k``'s tree is grown on gradients taken from the scores as they stand when
its turn comes, which already hold this iteration's trees of the classes
below ``k``, and not from the scores the iteration began with. Class 0's tree
is sound; every later class's is a sound tree of gradients one step stale in
the other direction.

What a planted fault is, and what ``iteration`` says: ``state_unchanged.py``.
"""
from __future__ import annotations

import contextlib


@contextlib.contextmanager
def planted(iteration: int = 1):
    from lightgbm_tpu.models.gbdt import GBDT

    grow = GBDT._train_tree

    def grow_on_moved_scores(self, grad_k, hess_k):
        if self.iter_ >= iteration:
            k = len(self.models) % self.num_tree_per_iteration   # the class whose turn it is
            grad, hess = self._compute_gradients(None)
            grad_k, hess_k = grad[k], hess[k]
        return grow(self, grad_k, hess_k)

    try:
        GBDT._train_tree = grow_on_moved_scores
        yield
    finally:
        GBDT._train_tree = grow
