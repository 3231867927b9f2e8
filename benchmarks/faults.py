"""Faults planted in the program at run time, for the control's readings and
for the harness's own tests: each breaks the timed path underneath an
otherwise ordinary run, and ``correct`` has to come out false. Nothing here
is used by a benchmark run. The program's files are not touched: a fault
wraps a method of ``lightgbm_tpu.models.gbdt.GBDT`` for the length of a
``with`` block.

state_unchanged   one iteration's score update is undone: the step returns
                  its state (the scores) as it got it
half_batch        every second row is left out of the histograms, so each
                  leaf's value is the mean over the rest
altered_answer    in the model that is handed out, two leaf values of one
                  tree change places, where they are produced

``iteration`` (from 0) says where: the one iteration the first and the last
act in, and the iteration from which on the second does. The default is the
second iteration, inside warm-up; a later one lies in the timed window.
"""
from __future__ import annotations

import contextlib

FAULTS = ("state_unchanged", "half_batch", "altered_answer")


@contextlib.contextmanager
def planted(name: str, iteration: int = 1):
    import jax.numpy as jnp

    from lightgbm_tpu.models.gbdt import GBDT

    if name not in FAULTS:
        raise KeyError(name)
    finish, grow = GBDT._finish_tree, GBDT._train_tree

    def finish_unchanged(self, tree_arrays, leaf_id, k, nl_dev):
        if self.iter_ != iteration:
            return finish(self, tree_arrays, leaf_id, k, nl_dev)
        kept = jnp.copy(self.scores)
        out = finish(self, tree_arrays, leaf_id, k, nl_dev)
        self.scores = kept
        return out

    def finish_altered(self, tree_arrays, leaf_id, k, nl_dev):
        out = finish(self, tree_arrays, leaf_id, k, nl_dev)
        if self.iter_ != iteration:
            return out
        v = out.leaf_value
        return out._replace(leaf_value=v.at[0].set(v[1]).at[1].set(v[0]))

    def grow_half(self, grad_k, hess_k):
        if self.iter_ < iteration:
            return grow(self, grad_k, hess_k)
        if getattr(self, "_bench_half_mask", None) is None:
            self._bench_half_mask = (jnp.arange(self.num_data) % 2 == 0).astype(
                self._bag_mask.dtype)
        self._bag_mask = self._bench_half_mask
        return grow(self, grad_k, hess_k)

    try:
        if name == "state_unchanged":
            GBDT._finish_tree = finish_unchanged
        elif name == "altered_answer":
            GBDT._finish_tree = finish_altered
        else:
            GBDT._train_tree = grow_half
        yield
    finally:
        GBDT._finish_tree, GBDT._train_tree = finish, grow
