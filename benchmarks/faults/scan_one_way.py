"""Planted fault ``scan_one_way``: from the planted iteration on the split
scan runs in one direction only, so no candidate that sends the missing to
the right is offered; the partition goes by the direction the tree states.

What a planted fault is, and what ``iteration`` says: ``state_unchanged.py``.
"""
from __future__ import annotations

import contextlib


@contextlib.contextmanager
def planted(iteration: int = 1):
    from lightgbm_tpu.models.gbdt import GBDT

    grow = GBDT._train_tree

    def grow_one_way(self, grad_k, hess_k):
        if self.iter_ >= iteration:
            self._two_way = False
        return grow(self, grad_k, hess_k)

    try:
        GBDT._train_tree = grow_one_way
        yield
    finally:
        GBDT._train_tree = grow
