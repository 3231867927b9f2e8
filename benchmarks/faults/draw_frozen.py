"""Planted fault ``draw_frozen``: from the planted iteration on every tree is
grown on the first tree's draw. The host stream moves as it should, each tree
is a sound tree of the columns it was handed and the record says truthfully
which those were: the same 80% of the table tree after tree, the other 20%
never seen. Only the law of the draw gives it away (``draw_mismatch``: a tree
whose draw is the draw of the tree before it).

What a planted fault is, and what ``iteration`` says: ``state_unchanged.py``.
"""
from __future__ import annotations

import contextlib


@contextlib.contextmanager
def planted(iteration: int = 1):
    from lightgbm_tpu.models.gbdt import GBDT

    draw = GBDT._draw_columns

    def draw_frozen(self, tree):
        cols = draw(self, tree)
        if cols is None:
            return None
        if getattr(self, "_bench_first_draw", None) is None:
            self._bench_first_draw = cols
        if self.iter_ < iteration:
            return cols
        self._column_draws[-1] = (tree, self._bench_first_draw)
        return self._bench_first_draw

    try:
        GBDT._draw_columns = draw_frozen
        yield
    finally:
        GBDT._draw_columns = draw
