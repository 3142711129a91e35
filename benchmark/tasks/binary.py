"""Task `binary`: 0/1 labels, `objective=binary` boosted from the label
average, quality = AUC.

The first-tree check is `reference.root_check`: at iteration 1 every row
has g = p0 - y and h = p0 (1 - p0), so the root's left count and gain
follow from the training column (made again from the seed), the labels
and the dumped threshold alone.
"""
from benchmark import reference

QUALITY = "auc"         # the end-to-end metric is holdout_auc_<trees>
GROUPED = False         # rows carry no query groups

# the documented arithmetic of the program, not slack: row counts are f32
# and fuzz above 2^24 rows (exact below; found up to 1.1e-6), and
# histogram sums are f32 (gain found off by up to 3.3e-6; PERF.md)
ROOT_COUNT_TOL = 4e-6
ROOT_GAIN_RTOL = 1e-3


def quality(pred, labels, groups) -> float:
    return reference.auc(pred, labels)


def first_tree(run) -> tuple:
    """({name: (number, limit)}, detail) for tree 0's root. `run` has
    `model` (the dump), `gen`, `rows`, `labels`, `groups`, `params` and
    `booster`."""
    root = reference.root_check(
        run.model, run.gen.column(run.model["tree_info"][0]["tree_structure"]
                                  ["split_feature"], 0, run.rows),
        run.labels, lambda_l2=float(run.params.get("lambda_l2", 0.0)))
    return {"root_left_count_err": (
                root["left_count_err"],
                ROOT_COUNT_TOL if run.rows > 1 << 24 else 0.0),
            "root_gain_rel_err": (root["gain_rel_err"], ROOT_GAIN_RTOL)}, root
