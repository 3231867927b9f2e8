"""Planted fault ``others_by_rank``: from the planted iteration on GOSS's
``other_k`` rows are not drawn: they are the next ``other_k`` rows by rank of
``|gradient x hessian|`` after the top. Counts, weights and multiplier are as
the law has them and the tree is a sound tree of its rows; only the
distribution of the drawn rows gives it away (``other_ks``).

What a planted fault is, and what ``iteration`` says: ``state_unchanged.py``.
"""
from __future__ import annotations

import contextlib


@contextlib.contextmanager
def planted(iteration: int = 1):
    import jax

    from lightgbm_tpu.models import goss

    bagging, sample, draw = goss.GOSS._bagging, goss.goss_sample, goss._draw_others
    # the sample program traced anew (a function of its own: jax keeps one
    # trace a function), with the rest left in its order of rank
    by_rank = jax.jit(lambda key, grad, hess, top_k, other_k: sample.__wrapped__(
        key, grad, hess, top_k, other_k), static_argnames=("top_k", "other_k"))

    def bagging_by_rank(self, iter_, grad, hess):
        planted_now = iter_ >= iteration
        goss.goss_sample = by_rank if planted_now else sample
        goss._draw_others = (lambda key, rest, other_k: rest[:other_k]) if planted_now else draw
        return bagging(self, iter_, grad, hess)

    try:
        goss.GOSS._bagging = bagging_by_rank
        yield
    finally:
        goss.GOSS._bagging, goss.goss_sample, goss._draw_others = bagging, sample, draw
