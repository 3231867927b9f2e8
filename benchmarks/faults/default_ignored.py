"""Planted fault ``default_ignored``: from the planted iteration on the
grower is handed a feature table that names no missing type. A row in its
split feature's NaN bin then goes by the threshold compare alone, which is to
the right, whatever direction the tree that is handed out states (the model
text takes the missing type from the bin mappers and the direction from the
one pass such a table is scanned in, which stands for "left"); no scan offers
the missing to the left either.

What a planted fault is, and what ``iteration`` says: ``state_unchanged.py``.
"""
from __future__ import annotations

import contextlib


@contextlib.contextmanager
def planted(iteration: int = 1):
    import jax.numpy as jnp

    from lightgbm_tpu.models.gbdt import GBDT

    grow = GBDT._train_tree

    def grow_ignoring(self, grad_k, hess_k):
        if self.iter_ < iteration:
            return grow(self, grad_k, hess_k)
        if getattr(self, "_bench_meta_as_was", None) is None:
            self._bench_meta_as_was = self.feature_meta
            self.feature_meta = dict(
                self.feature_meta,
                missing_type=jnp.zeros_like(self.feature_meta["missing_type"]))
        return grow(self, grad_k, hess_k)

    try:
        GBDT._train_tree = grow_ignoring
        yield
    finally:
        GBDT._train_tree = grow
