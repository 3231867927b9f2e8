"""Planted fault ``class_rotated``: from the planted iteration on the tree
grown on class ``c``'s gradients moves class ``c + 1``'s scores and is stored
in its place (the last class's goes to class 0). The model text and the
scores agree with each other; each tree stands where another class's should.

What a planted fault is, and what ``iteration`` says: ``state_unchanged.py``.
"""
from __future__ import annotations

import contextlib


@contextlib.contextmanager
def planted(iteration: int = 1):
    from lightgbm_tpu.models.gbdt import GBDT

    gradients, grow = GBDT._compute_gradients, GBDT._train_tree

    def gradients_kept(self, init_scores):
        self._bench_gradients = gradients(self, init_scores)
        return self._bench_gradients

    def grow_rotated(self, grad_k, hess_k):
        if self.iter_ >= iteration:
            K = self.num_tree_per_iteration
            k = len(self.models) % K                 # the place the tree is stored in
            grad, hess = self._bench_gradients
            grad_k, hess_k = grad[(k - 1) % K], hess[(k - 1) % K]
        return grow(self, grad_k, hess_k)

    try:
        GBDT._compute_gradients, GBDT._train_tree = gradients_kept, grow_rotated
        yield
    finally:
        GBDT._compute_gradients, GBDT._train_tree = gradients, grow
