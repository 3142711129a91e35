"""Plain numpy reference: what a model's dump says its trees compute,
and what an objective's gradients and a metric are.

Independent of the program: it imports nothing of it, and reads only
`Booster.dump_model()`'s JSON, raw feature values, labels and query
sizes. The harness holds the program to it in these ways: the program's
own `predict` has to agree with `raw_scores` (the walk); tree 0's root
has to be the split that the training column and the first gradients
give in numpy (`root_check` where the gradients follow from the labels,
`root_from_gradients` where they are a vector); a ranking objective's
gradients have to be `lambdarank_gradients`; and the holdout quality is
computed here (`auc`, `ndcg_at`).

`/root/reference` is not mounted in this sandbox, so the ranking part is
written from the lines of LightGBM 2.2.4's
`src/objective/rank_objective.hpp` and `src/metric/dcg_calculator.cpp`
as `SURVEY.md` and `lightgbm_tpu/ops/ranking.py` cite them
(`GetGradientsForOneQuery` :82-160, inverse max DCG :58-69, the sigmoid
table :71; `DCGCalculator::Init`, `CalMaxDCGAtK` :53-77, `CalDCGAtK`),
and from memory of that header. Each departure from it is a comment.
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


def root_from_gradients(model: dict, column: np.ndarray, grad: np.ndarray,
                        hess: np.ndarray, lambda_l2: float = 0.0) -> dict:
    """Tree 0's root against the whole training column and the first
    iteration's gradient and hessian vectors, summed here in float64: the
    same two errors as `root_check`, for an objective whose gradients do
    not follow from a row's own label."""
    root = model["tree_info"][0]["tree_structure"]
    g = np.asarray(grad, np.float64)
    h = np.asarray(hess, np.float64)
    left = _goes_left(column, root)
    n, n_left = len(g), int(left.sum())
    g_left, h_left = g[left].sum(), h[left].sum()
    g_all, h_all = g.sum(), h.sum()

    def term(gs, hs):
        return gs * gs / (hs + lambda_l2)
    gain = term(g_left, h_left) + term(g_all - g_left, h_all - h_left) \
        - term(g_all, h_all)
    return {"feature": root["split_feature"],
            "left_count": n_left, "left_count_model": _count(root["left_child"]),
            "left_count_err": abs(n_left - _count(root["left_child"])) / n,
            "gain": gain, "gain_model": root["split_gain"],
            "gain_rel_err": abs(gain - root["split_gain"]) / abs(gain)}


# ---- ranking: rank_objective.hpp and dcg_calculator.cpp at 2.2.4

def label_gain(label: np.ndarray) -> np.ndarray:
    """2^label - 1, the default `label_gain` table."""
    return np.exp2(np.asarray(label, np.float64)) - 1.0


def discounts(n: int) -> np.ndarray:
    """1 / log2(2 + position) (`DCGCalculator::Init`)."""
    return 1.0 / np.log2(2.0 + np.arange(n, dtype=np.float64))


def max_dcg_at(k: int, label: np.ndarray) -> float:
    """DCG of the best order's first k (`CalMaxDCGAtK`)."""
    top = np.sort(label_gain(label))[::-1][:k]
    return float((top * discounts(len(top))).sum())


def ndcg_at(k: int, score: np.ndarray, label: np.ndarray,
            groups: np.ndarray) -> float:
    """Mean over the queries of DCG@k of the score order over the best
    order's. A query with no relevant document counts 1, as
    `NDCGMetric::Eval` counts it. Departure: the header orders with
    `std::sort`, which leaves ties in any order; here ties keep the
    documents' order (a stable sort), so that the number is one number."""
    score = np.asarray(score, np.float64)
    total, at = 0.0, 0
    for n in np.asarray(groups, np.int64):
        s, lab = score[at:at + n], np.asarray(label[at:at + n])
        at += n
        best = max_dcg_at(k, lab)
        if best <= 0.0:
            total += 1.0
            continue
        first = np.argsort(-s, kind="stable")[:k]
        total += float((label_gain(lab[first])
                        * discounts(len(first))).sum()) / best
    return total / len(groups)


def lambdarank_gradients(score: np.ndarray, label: np.ndarray,
                         groups: np.ndarray, max_position: int = 20,
                         sigmoid: float = 1.0, dtype=np.float64):
    """(gradient, hessian) of every document, `GetGradientsForOneQuery`
    query by query: documents ordered by score (stable, descending); for
    every pair of a higher and a lower grade the change of NDCG if the two
    swapped places, (gain_high - gain_low) x |discount_i - discount_j| x
    the query's inverse max DCG at `max_position`, divided by 0.01 +
    |score difference| where the query's best and worst scores differ;
    times the pair's sigmoid 2 / (1 + exp(2 sigmoid ds)) for the gradient
    and p (2 - p) x 2 for the hessian. At 2.2.4 `max_position` enters the
    inverse max DCG alone: every pair of the query counts, at any depth.

    Departures from the header: the sigmoid is evaluated and not looked up
    in its table of 2^20 cells over [-50, 50] (the cell is 1e-4 wide, so
    the table is off by under 1e-4 relative); no score is `kMinScore` and
    there are no weights; sums are in `dtype` throughout, where the header
    adds the lower document's share in float32. `dtype` below float64 is
    for the control alone: pair factors AND sums in that type."""
    score = np.asarray(score, np.float64)
    grad = np.zeros(len(score), np.float64)
    hess = np.zeros(len(score), np.float64)
    at = 0
    for n in np.asarray(groups, np.int64):
        s, lab = score[at:at + n], np.asarray(label[at:at + n], np.float64)
        best = max_dcg_at(max_position, lab)
        if best > 0.0 and n > 1:
            order = np.argsort(-s, kind="stable")
            ss, sl = s[order], lab[order]
            gain, disc = label_gain(sl), discounts(n)
            ds = (ss[:, None] - ss[None, :]).astype(dtype)
            delta = ((gain[:, None] - gain[None, :]).astype(dtype)
                     * np.abs(disc[:, None] - disc[None, :]).astype(dtype)
                     * dtype(1.0 / best))
            if ss[0] != ss[-1]:
                delta = delta / (dtype(0.01) + np.abs(ds))
            p = (2.0 / (1.0 + np.exp(2.0 * sigmoid
                                     * ds.astype(np.float64)))).astype(dtype)
            pair = sl[:, None] > sl[None, :]    # row: the higher grade
            zero = dtype(0.0)
            lam = np.where(pair, -p * delta, zero)
            hes = np.where(pair, p * (dtype(2.0) - p) * dtype(2.0) * delta,
                           zero)
            # the higher document takes +lambda, the lower -lambda; both
            # take the hessian
            g = lam.sum(axis=1, dtype=dtype) - lam.sum(axis=0, dtype=dtype)
            h = hes.sum(axis=1, dtype=dtype) + hes.sum(axis=0, dtype=dtype)
            grad[at + order] = g.astype(np.float64)
            hess[at + order] = h.astype(np.float64)
        at += n
    return grad, hess
