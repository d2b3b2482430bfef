"""Plain numpy reference for the GBDT configurations: the exact root split of
the first tree, a tree walk over a fitted booster's arrays, and (the
builder's tool, at the bottom) the accuracy an independent histogram booster
reaches on the same data.  Nothing here imports the program.
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import Dict, Tuple

import numpy as np


def root_split_gains(binned: np.ndarray, y: np.ndarray, bins: int,
                     min_data_in_leaf: int, min_sum_hessian: float,
                     lambda_l2: float = 0.0, threads: int = 8) -> np.ndarray:
    """``(features, bins - 1)`` split gains of the root of the first tree of
    a binary-logloss booster that starts from the mean label: every row has
    gradient ``p - y`` and hessian ``p (1 - p)`` with ``p = mean(y)``, so a
    histogram needs only the rows and the positives per bin.  ``gain[f, b]``
    is that of sending bin ``<= b`` left; a split that leaves either side
    short of rows or hessian is ``-inf``."""
    n, features = binned.shape
    p = float(np.clip(y.mean(), 1e-6, 1 - 1e-6))
    pos_off = (y > 0.5).astype(np.int64) * bins

    def one(f: int) -> np.ndarray:
        idx = binned[:, f].astype(np.int64)
        idx += pos_off
        return np.bincount(idx, minlength=2 * bins)[:2 * bins]

    with ThreadPoolExecutor(max(1, threads)) as pool:
        both = np.stack(list(pool.map(one, range(features))))
    neg, pos = both[:, :bins].astype(np.float64), both[:, bins:].astype(np.float64)
    cnt = neg + pos
    grad = p * cnt - pos                       # sum of (p - y) per bin
    hess = p * (1 - p) * cnt
    cl, gl, hl = (np.cumsum(a, axis=1)[:, :-1] for a in (cnt, grad, hess))
    cr, gr, hr = n - cl, grad.sum(1, keepdims=True) - gl, \
        hess.sum(1, keepdims=True) - hl
    with np.errstate(divide="ignore", invalid="ignore"):
        gain = gl ** 2 / (hl + lambda_l2) + gr ** 2 / (hr + lambda_l2)
    ok = (cl >= min_data_in_leaf) & (cr >= min_data_in_leaf) \
        & (hl >= min_sum_hessian) & (hr >= min_sum_hessian)
    return np.where(ok, gain, -np.inf)


def check_root_split(gains: np.ndarray, feature: int, threshold_bin: int,
                     gain_slack: float = 1e-3) -> Tuple[bool, str]:
    """The booster's root must split the feature the exact search picks, at
    its bin or a neighbour, or at a bin whose exact gain is within
    ``gain_slack`` of the best (near the optimum the gain is flat, and
    quantized gradients may settle anywhere on the plateau)."""
    best_f, best_b = np.unravel_index(np.argmax(gains), gains.shape)
    best = gains[best_f, best_b]
    got = gains[feature, threshold_bin] \
        if 0 <= threshold_bin < gains.shape[1] else -np.inf
    note = (f"root split (feature {feature}, bin {threshold_bin}) vs exact "
            f"(feature {best_f}, bin {best_b}); gain ratio "
            f"{got / best if best > 0 else float('nan'):.6f}")
    ok = feature == best_f and (abs(threshold_bin - best_b) <= 1
                                or got >= (1 - gain_slack) * best)
    return bool(ok), note


def predict_proba(booster: Dict[str, np.ndarray], X: np.ndarray) -> np.ndarray:
    """P(y = 1) from a booster's arrays: ``split_feature``, ``threshold``
    (``x <= threshold`` goes left), ``left_child`` / ``right_child`` (a
    negative child ``c`` is leaf ``~c``), ``leaf_value``, ``tree_weight``,
    and the scalars ``init_score`` and ``sigmoid``."""
    n = X.shape[0]
    raw = np.full(n, float(booster["init_score"]), np.float64)
    rows = np.arange(n)
    for t in range(booster["split_feature"].shape[0]):
        sf, th = booster["split_feature"][t], booster["threshold"][t]
        lc, rc = booster["left_child"][t], booster["right_child"][t]
        node = np.zeros(n, np.int64)
        for _ in range(sf.shape[0] + 1):       # no path is longer than the tree
            live = node >= 0
            if not live.any():
                break
            j = node[live]
            f = sf[j]
            x = X[rows[live], np.maximum(f, 0)]
            right = (f >= 0) & (x > th[j])
            node[live] = np.where(right, rc[j], lc[j])
        if (node >= 0).any():
            raise ValueError(f"tree {t}: a walk did not reach a leaf")
        raw += booster["leaf_value"][t][~node] * booster["tree_weight"][t]
    return 1.0 / (1.0 + np.exp(-float(booster["sigmoid"]) * raw))


def booster_arrays(booster) -> Dict[str, np.ndarray]:
    """The fields ``predict_proba`` reads, from a fitted booster object."""
    keys = ("split_feature", "threshold", "left_child", "right_child",
            "leaf_value", "tree_weight", "init_score", "sigmoid")
    return {k: getattr(booster, k) for k in keys}


def holdout_accuracy(booster: Dict[str, np.ndarray], X: np.ndarray,
                     y: np.ndarray) -> float:
    return float(((predict_proba(booster, X) > 0.5) == (y > 0.5)).mean())


def _main() -> None:
    """The builder's tool: the held-out accuracy scikit-learn's
    ``HistGradientBoostingClassifier`` reaches on a configuration's data, at
    the same depth, iterations and rate.  Run on the CPU; the values go into
    the configuration file under ``reference``.

        python3 benchmark/families/gbdt_reference.py <config.json> <seed>...
    """
    import json
    import os
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    from sklearn.ensemble import HistGradientBoostingClassifier
    from benchmark import datagen
    with open(sys.argv[1]) as f:
        cfg = json.load(f)
    for seed in (int(s) for s in sys.argv[2:]):
        X, y, Xh, yh = datagen.tabular(seed, cfg["rows"], cfg["features"],
                                       cfg["block_rows"], cfg["holdout_rows"],
                                       cfg["label_noise"])
        p = cfg["params"]
        model = HistGradientBoostingClassifier(
            max_iter=cfg["iterations_per_fit"], max_depth=p["max_depth"],
            learning_rate=p["learning_rate"], max_bins=p["max_bin"],
            max_leaf_nodes=None, min_samples_leaf=20, early_stopping=False,
            random_state=0).fit(X, y)
        print(json.dumps({"seed": seed, "holdout_accuracy":
                          float(model.score(Xh, yh))}), flush=True)


if __name__ == "__main__":
    _main()
