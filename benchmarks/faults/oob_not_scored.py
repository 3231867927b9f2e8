"""Planted fault ``oob_not_scored``: from the planted iteration on the rows
out of the bag miss the tree's score update: the tree is grown and handed
out as it should be, but only the rows it was grown on move. An iteration
that grew on every row has no such rows.

What a planted fault is, and what ``iteration`` says: ``state_unchanged.py``.
"""
from __future__ import annotations

import contextlib


@contextlib.contextmanager
def planted(iteration: int = 1):
    import jax.numpy as jnp

    from lightgbm_tpu.models.gbdt import GBDT

    finish = GBDT._finish_tree

    def finish_in_bag_alone(self, tree_arrays, leaf_id, k, nl_dev):
        if self.iter_ < iteration:
            return finish(self, tree_arrays, leaf_id, k, nl_dev)
        kept = jnp.copy(self.scores)
        out = finish(self, tree_arrays, leaf_id, k, nl_dev)
        self.scores = jnp.where(self._bag_mask > 0, self.scores, kept)
        return out

    try:
        GBDT._finish_tree = finish_in_bag_alone
        yield
    finally:
        GBDT._finish_tree = finish
