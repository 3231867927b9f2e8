"""Planted fault ``amp_dropped``: from the planted iteration on the rows
GOSS draws from the small gradients keep weight 1: the draw and its record
are sound, but the gradients and hessians handed to the grower are the
unamplified ones, so every leaf's value leans toward the rows with large
gradients (the bias the multiplier ``(n - top_k) / other_k`` is there to take
out).

What a planted fault is, and what ``iteration`` says: ``state_unchanged.py``.
An iteration before sampling starts (``1 / learning_rate``) has nothing to
drop.
"""
from __future__ import annotations

import contextlib


@contextlib.contextmanager
def planted(iteration: int = 1):
    from lightgbm_tpu.models.goss import GOSS

    bagging = GOSS._bagging

    def bagging_unamplified(self, iter_, grad, hess):
        amplified = bagging(self, iter_, grad, hess)
        return (grad, hess) if iter_ >= iteration else amplified

    try:
        GOSS._bagging = bagging_unamplified
        yield
    finally:
        GOSS._bagging = bagging
