"""Planted fault ``index_not_mapped``: from the planted iteration on the tree
that is handed out keeps the column numbers of its draw's space: a split on
the draw's ``j``-th column is written as a split on column ``j`` of the table.
The rows' leaves, and so the training scores, are those of the tree as it was
grown; the model text, a validation set's scores and every later prediction
read another tree.

What a planted fault is, and what ``iteration`` says: ``state_unchanged.py``.
"""
from __future__ import annotations

import contextlib


@contextlib.contextmanager
def planted(iteration: int = 1):
    from lightgbm_tpu.models import gbdt

    grow, mapped = gbdt.GBDT._train_tree, gbdt._table_columns

    def grow_unmapped(self, grad_k, hess_k):
        gbdt._table_columns = (
            (lambda split_feature, num_leaves, cols: split_feature)
            if self.iter_ >= iteration else mapped)
        try:
            return grow(self, grad_k, hess_k)
        finally:
            gbdt._table_columns = mapped

    try:
        gbdt.GBDT._train_tree = grow_unmapped
        yield
    finally:
        gbdt.GBDT._train_tree, gbdt._table_columns = grow, mapped
