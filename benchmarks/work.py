"""The work the grown trees needed, whatever implemented them.

Counted from the trees alone (their node counts), so that a change of kernel
cannot move the yardstick. For one tree over N rows, F features, B bins:

- histograms: the root's N rows, and for each split the rows of its smaller
  child (the larger child's histogram is the parent's minus the smaller's).
  Each such row reads F one-byte bins and 12 bytes of gradient, hessian and
  count, and costs F x 3 x 2 operations (one multiply-add into each of the
  three sums per feature);
- partition and score update: one pass over all N rows, F + 8 bytes a row;
- split scans: 2 x F x B x 20 operations per split (two children, a prefix
  sum and a gain per bin).

The least time is the larger of bytes over the chip's bandwidth and
operations over its peak rate; ``bound`` says which.
"""
from __future__ import annotations

from typing import Dict, Iterable

import numpy as np

HIST_BYTES_PER_ROW_EXTRA = 12
HIST_OPS_PER_ROW_FEATURE = 3 * 2
PASS_BYTES_PER_ROW_EXTRA = 8
SCAN_OPS_PER_BIN = 20


def smaller_child_rows(tree: Dict[str, np.ndarray]) -> int:
    """Sum over the tree's splits of the rows in the smaller child."""
    def count(child: int) -> int:
        return int(tree["leaf_count"][-(child + 1)] if child < 0
                   else tree["internal_count"][child])

    return sum(min(count(int(l)), count(int(r)))
               for l, r in zip(tree["left_child"], tree["right_child"]))


def tree_work(tree: Dict[str, np.ndarray], rows: int, features: int, bins: int
              ) -> Dict[str, float]:
    splits = int(tree["num_leaves"]) - 1
    if splits < 1:
        return {"bytes": 0.0, "ops": 0.0, "hist_rows": 0.0}
    hist_rows = rows + smaller_child_rows(tree)
    return {
        "hist_rows": float(hist_rows),
        "bytes": float(hist_rows * (features + HIST_BYTES_PER_ROW_EXTRA)
                       + rows * (features + PASS_BYTES_PER_ROW_EXTRA)),
        "ops": float(hist_rows * features * HIST_OPS_PER_ROW_FEATURE
                     + splits * 2 * features * bins * SCAN_OPS_PER_BIN),
    }


def least_seconds(trees: Iterable[Dict[str, np.ndarray]], rows: int, features: int,
                  bins: int, peak: Dict[str, float]) -> Dict[str, object]:
    """The least time the chip could take for these trees, and its bound."""
    total_bytes = total_ops = 0.0
    for t in trees:
        w = tree_work(t, rows, features, bins)
        total_bytes += w["bytes"]
        total_ops += w["ops"]
    by_bytes = total_bytes / peak["bytes_per_s"]
    by_ops = total_ops / peak["flops"]
    return {"seconds": max(by_bytes, by_ops),
            "bound": "bytes" if by_bytes >= by_ops else "operations",
            "bytes": total_bytes, "ops": total_ops}
