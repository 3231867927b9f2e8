"""Parses the LightGBM model text the program hands out into arrays.

The text is the program's public output (``Booster.model_to_string``); this
reader shares no code with the program's own writer or loader.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

_INT = ("split_feature", "left_child", "right_child", "leaf_count", "internal_count")
_FLOAT = ("split_gain", "threshold", "leaf_value", "internal_value")


def parse_trees(text: str) -> List[Dict[str, np.ndarray]]:
    """One dict per tree: ``num_leaves``, ``shrinkage`` and the arrays above.
    A tree that did not split has ``num_leaves`` 1 and a single leaf value."""
    trees = []
    for section in text.split("\nTree=")[1:]:
        fields = {}
        for line in section.splitlines()[1:]:
            if line.startswith("end of trees") or not line.strip():
                break
            key, _, value = line.partition("=")
            fields[key] = value
        tree = {"num_leaves": int(fields["num_leaves"]),
                "shrinkage": float(fields.get("shrinkage", 1.0))}
        for key in _INT:
            tree[key] = np.array(fields.get(key, "").split(), dtype=np.int64)
        for key in _FLOAT:
            tree[key] = np.array(fields.get(key, "").split(), dtype=np.float64)
        trees.append(tree)
    return trees
