"""GOSS boosting (Gradient-based One-Side Sampling).

TPU-native counterpart of /root/reference/src/boosting/goss.hpp: keep the top
``top_rate`` fraction of rows by sum_k |grad_k * hess_k|, sample ``other_rate`` of
the rest, and amplify the sampled small-gradient rows' grad/hess by
(n - top_k) / other_k (goss.hpp:91-141). The draw runs over the whole table, as
Algorithm 2 of the paper (Ke et al., NIPS 2017) has it; the reference draws
per thread block. Like the reference, no subsampling for the first
1/learning_rate iterations (goss.hpp:143-146).

The sample is work saved: the draw is handed to the grower as ``_bag_mask``,
and the serial grower roots the tree at the in-bag rows (ops/grow.py
``grow_tree``), as the reference's learner is handed the in-bag indices; the
rows out of the bag get the tree's score by the finished tree. Each draw is
kept, packed to bits, for ``GBDT.sample_draws``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..obs import trace as trace_mod
from ..utils import log
from .gbdt import GBDT


def _draw_others(key, rest, other_k: int):
    """``other_k`` of the rows in ``rest``, drawn uniformly."""
    return rest[jax.random.permutation(key, rest.shape[0])][:other_k]


@functools.partial(jax.jit, static_argnames=("top_k", "other_k"))
def goss_sample(key, grad, hess, top_k: int, other_k: int):
    """On-device GOSS subset: top_k rows by sum_k |g*h| kept, other_k sampled
    from the rest with gradients amplified by (n-top_k)/other_k (goss.hpp:91-141).
    Returns the amplified gradients and hessians, the in-bag mask and, packed
    to bits, the in-bag rows and those that carry the multiplier.

    lax-native counterpart of the reference's host argsort + RNG loop — no
    N-sized device->host transfer per iteration."""
    n = grad.shape[1]
    score = jnp.sum(jnp.abs(grad * hess), axis=0)
    order = jnp.argsort(-score, stable=True)
    other_idx = _draw_others(key, order[top_k:], other_k)
    drawn = jnp.zeros((n,), bool).at[other_idx].set(True)
    in_bag = drawn.at[order[:top_k]].set(True)
    amp = jnp.where(drawn, jnp.float32((n - top_k) / other_k), jnp.float32(1.0))
    bits = jnp.stack([jnp.packbits(in_bag), jnp.packbits(drawn)])
    return grad * amp[None, :], hess * amp[None, :], in_bag.astype(jnp.float32), bits


class GOSS(GBDT):
    def _setup_train(self, train_set):
        super()._setup_train(train_set)
        cfg = self.config
        if cfg.top_rate + cfg.other_rate > 1.0:
            log.fatal("top_rate + other_rate must be <= 1.0 in GOSS")
        if cfg.top_rate <= 0 or cfg.other_rate <= 0:
            log.fatal("top_rate and other_rate must be positive in GOSS")
        if cfg.bagging_freq > 0 and cfg.bagging_fraction != 1.0:
            log.fatal("Cannot use bagging in GOSS")
        self._bag_all = jnp.ones((self.num_data,), jnp.float32)
        log.info("Using GOSS")

    def _bagging(self, iter_, grad, hess):
        cfg = self.config
        n = self.num_data
        top_k = max(1, int(n * cfg.top_rate))
        other_k = min(max(1, int(n * cfg.other_rate)), n - top_k)
        with trace_mod.span("train.sample", cat="train"):
            # no subsampling for the first 1/lr iterations (goss.hpp:143-146),
            # nor where top_rate covers every row
            self._bagging_active = iter_ >= int(1.0 / cfg.learning_rate)
            if not self._bagging_active or other_k <= 0:
                self._bag_mask = self._bag_all
                self._note_sample(iter_, n, 0, 1.0)
                return grad, hess
            key = jax.random.fold_in(self._bag_key, iter_)
            grad, hess, self._bag_mask, bits = goss_sample(
                key, grad, hess, top_k, other_k)
            self._note_sample(iter_, top_k, other_k, (n - top_k) / other_k, bits)
            return grad, hess
