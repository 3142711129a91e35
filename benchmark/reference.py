"""Plain numpy reference: what a model's dump says its trees compute.

Independent of the program: it reads only `Booster.dump_model()`'s JSON
and raw feature values. The harness holds the program to it in three
ways: the program's own `predict` has to agree with `raw_scores` (the
walk), tree 0's root has to be the split that the training column and
labels give in numpy (`root_check`), and the holdout AUC is computed here
(`auc`).
"""
import numpy as np

ZERO = 1e-35    # LightGBM's kZeroThreshold


def _goes_left(x: np.ndarray, node: dict) -> np.ndarray:
    """LightGBM's NumericalDecision for one split over a column."""
    if node["decision_type"] != "<=":
        raise ValueError("reference walk handles numerical splits only, "
                         f"got decision_type {node['decision_type']!r}")
    x = np.asarray(x, np.float64)
    nan = np.isnan(x)
    kind = node["missing_type"]
    if kind == "NaN":
        missing = nan
    elif kind == "Zero":
        missing = nan | (np.abs(x) <= ZERO)
    else:
        missing = np.zeros(len(x), bool)
        x = np.where(nan, 0.0, x)
    with np.errstate(invalid="ignore"):
        left = x <= node["threshold"]
    return np.where(missing, bool(node["default_left"]), left)


def raw_scores(model: dict, x: np.ndarray) -> np.ndarray:
    """Sum of the leaf values each row of `x` reaches, over all trees of
    the dump (one tree per iteration)."""
    out = np.zeros(len(x), np.float64)
    for tree in model["tree_info"]:
        stack = [(tree["tree_structure"], np.arange(len(x)))]
        while stack:
            node, rows = stack.pop()
            if "leaf_value" in node:
                out[rows] += node["leaf_value"]
                continue
            left = _goes_left(x[rows, node["split_feature"]], node)
            stack.append((node["left_child"], rows[left]))
            stack.append((node["right_child"], rows[~left]))
    return out


def auc(score: np.ndarray, label: np.ndarray) -> float:
    """Area under the ROC curve, ties given their average rank."""
    label = np.asarray(label) > 0
    _, inverse, counts = np.unique(score, return_inverse=True,
                                   return_counts=True)
    last = np.cumsum(counts)
    rank = (last - (counts - 1) / 2.0)[inverse]
    pos = float(label.sum())
    neg = float(len(label)) - pos
    return float((rank[label].sum() - pos * (pos + 1) / 2.0) / (pos * neg))


def _count(node: dict) -> int:
    return node["leaf_count"] if "leaf_count" in node \
        else node["internal_count"]


def root_check(model: dict, column: np.ndarray, label: np.ndarray,
               lambda_l2: float = 0.0) -> dict:
    """Tree 0's root against the whole training column, for
    `objective=binary` boosted from the label average: every row has
    g = p0 - y and h = p0 (1 - p0), so the root's left count and gain
    follow from the column, the labels and the dumped threshold alone.

    Returns the two relative errors; the caller holds them to its
    tolerances (f32 counts above 2^24 rows and f32 histogram sums are the
    program's documented arithmetic, not this function's)."""
    root = model["tree_info"][0]["tree_structure"]
    y = np.asarray(label, np.float64)
    n = len(y)
    p0 = y.mean()
    h = p0 * (1.0 - p0)
    left = _goes_left(column, root)
    n_left = int(left.sum())
    g_left = n_left * p0 - y[left].sum()
    g_all = n * p0 - y.sum()
    g_right = g_all - g_left

    def term(g, cnt):
        return g * g / (cnt * h + lambda_l2)
    gain = term(g_left, n_left) + term(g_right, n - n_left) - term(g_all, n)
    return {"feature": root["split_feature"],
            "left_count": n_left, "left_count_model": _count(root["left_child"]),
            "left_count_err": abs(n_left - _count(root["left_child"])) / n,
            "gain": gain, "gain_model": root["split_gain"],
            "gain_rel_err": abs(gain - root["split_gain"]) / abs(gain)}
