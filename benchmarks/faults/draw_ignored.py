"""Planted fault ``draw_ignored``: from the planted iteration on the grower is
handed every column of the table whatever the tree's draw says. The draw is
made and recorded as the law has it, and the tree is a sound tree of all the
columns: what gives it away is a split on a column its draw does not hold,
and that it cost a full tree (``featfrac.hist_columns_pct`` reads 100).

What a planted fault is, and what ``iteration`` says: ``state_unchanged.py``.
"""
from __future__ import annotations

import contextlib


@contextlib.contextmanager
def planted(iteration: int = 1):
    from lightgbm_tpu.models.gbdt import GBDT

    reason, mask = GBDT.column_draw_fallback_reason, GBDT._columns_mask

    def reason_ignoring(self):
        return "planted: draw_ignored" if self.iter_ >= iteration else reason(self)

    def mask_ignoring(self, cols):
        return mask(self, None if self.iter_ >= iteration else cols)

    try:
        GBDT.column_draw_fallback_reason, GBDT._columns_mask = reason_ignoring, mask_ignoring
        yield
    finally:
        GBDT.column_draw_fallback_reason, GBDT._columns_mask = reason, mask
