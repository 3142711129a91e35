"""Task `lambdarank`: graded labels in query groups,
`objective=lambdarank`, quality = NDCG@10 over whole holdout queries.

The gradients of a ranking objective do not follow from a row's own
label, and the reference over all 3.3e9 pairs of 10.45M rows in numpy
would cost more than the run. So the first-tree check is a chain, every
link on what the timed path computes:

(a) the program's gradients at the start scores (all zero: lambdarank
    boosts from no average), from the objective object the booster
    trains with, called as the training loop calls it (the fused kernel
    on the chip, over every row), against
    `reference.lambdarank_gradients` on a sample of `GRAD_QUERIES`
    queries drawn from the seed over the whole set, the first and the
    last query always among them (`sampled_queries`): largest absolute
    difference over the reference's largest gradient, for gradient and
    hessian. A fault past the head of the set (queries dropped or zeroed
    on later tiles, a bad last tile) meets the sample wherever it sits;
(b) tree 0's root from the dumped threshold, the whole training column
    made again from the seed, and the program's gradient vector of (a)
    summed in float64 (`reference.root_from_gradients`): left count
    exact, gain within 1e-3 relative;
(c) at the trained scores, where the sort, the sigmoid and the division
    by 0.01 + |score difference| do something: the program's gradients
    at its own scores against the reference's at the same scores, on the
    same sample of queries; and the program's training scores against
    the numpy walk over the dumped trees on `WALK_ROWS` rows, a stretch
    in each of a few of the generator's blocks (the first, the last, the
    others drawn from the seed: `walk_stretches`), because a row's
    values cost its whole block to make again.

(a) ties the gradients to the reference, (b) the tree to the gradients,
(c) the state the window leaves to the trees, and the gradient code to
the reference where scores differ.
"""
import concurrent.futures

import numpy as np

from benchmark import reference

QUALITY = "ndcg10"      # the end-to-end metric is holdout_ndcg10_<trees>
GROUPED = True          # the generator gives `groups(first_row, rows)`

GRAD_QUERIES = 256      # about 81,000 of Istella's rows, 0.8%
WALK_ROWS = 81920       # as many, in WALK_BLOCKS stretches
WALK_BLOCKS = 4
# The kernel forms pair factors in bf16 (8 bits: 0.4% a factor) and sums
# them in f32 (config.py: tpu_rank_fused), so a document's gradient is off
# by a fraction of a percent of the largest. Lower readings, the program
# on the chip: 0.0013-0.0034 at the start scores and 0.0023-0.0048 at the
# trained ones over 13 seeds' samples (0.0011-0.0051 over 26 seeds on the
# head of the set, before the sample was drawn). Upper readings, the
# reference with pair factors AND sums in bf16 (the step that would tempt a
# later PR) on the same 12 samples: 0.085-0.258 and 0.200-0.420
# (selftest/control_rank_bf16.py; PERF.md section 2). Gradients dropped
# from a sampled query read 1. The limit sits between them.
GRAD_TOL = 2e-2
SCORE_WALK_TOL = 1e-5   # f32 sums of a few leaf values against float64
ROOT_COUNT_TOL = 4e-6   # f32 row counts fuzz above 2^24 rows, exact below
ROOT_GAIN_RTOL = 1e-3   # f32 histogram sums of f32 gradients


def quality(pred, labels, groups) -> float:
    return reference.ndcg_at(10, pred, labels, groups)


def program_gradients(booster, score: np.ndarray):
    """(g, h) of every row at `score`, from the objective the booster
    trains with, through the call the training loop makes
    (`gbdt._dispatch_aligned`)."""
    import jax.numpy as jnp
    g, h = booster._gbdt.objective.get_gradients(
        jnp.asarray(score, jnp.float32)[None, :])
    return np.asarray(g[0]), np.asarray(h[0])


def worst(got: np.ndarray, want: np.ndarray) -> float:
    scale = float(np.abs(want).max())
    return float(np.abs(got - want).max()) / scale if scale > 0 \
        else float("inf")


def sampled_queries(seed: int, queries: int, take: int) -> np.ndarray:
    """`take` query numbers in order: the first, the last, and the others
    drawn from the seed without replacement (all of them where there are
    no more than `take`)."""
    if queries <= take:
        return np.arange(queries)
    inner = np.random.default_rng([int(seed), 3]).choice(
        np.arange(1, queries - 1), size=take - 2, replace=False)
    return np.sort(np.concatenate([[0], inner, [queries - 1]]))


def walk_stretches(seed: int, rows: int, block_rows: int, blocks: int,
                   take: int) -> list:
    """[lo, hi) row ranges, each inside one block of the generator: in
    the first block from row 0, in the last up to the last row, in
    `blocks` - 2 others (drawn from the seed) from a drawn offset;
    `take` rows in all, or every row where there are fewer."""
    if rows <= take:
        return [(0, rows)]
    rng = np.random.default_rng([int(seed), 4])
    last = (rows - 1) // block_rows
    inner = rng.choice(np.arange(1, last), size=min(blocks - 2, last - 1),
                       replace=False) if last > 1 else []
    each = min(take // (2 + len(inner)), block_rows)
    out = [(0, each), (max(rows - each, last * block_rows), rows)]
    for blk in inner:
        lo = int(blk) * block_rows + int(rng.integers(block_rows - each + 1))
        out.append((lo, lo + each))
    return sorted(out)


def first_tree(run) -> tuple:
    """({name: (number, limit)}, detail): see the module's docstring."""
    groups = np.asarray(run.groups, np.int64)
    bounds = np.concatenate([[0], np.cumsum(groups)])
    picked = sampled_queries(run.gen.seed, len(groups), GRAD_QUERIES)
    sizes = groups[picked]
    at = np.concatenate([np.arange(bounds[q], bounds[q + 1])
                         for q in picked])
    labels = run.labels[at]
    kw = {"max_position": int(run.params.get("max_position", 20)),
          "sigmoid": float(run.params.get("sigmoid", 1.0))}
    obj = run.booster._gbdt.objective
    # (a), (b): the first iteration
    g, h = program_gradients(run.booster, np.zeros(run.rows, np.float32))
    g_ref, h_ref = reference.lambdarank_gradients(np.zeros(len(at)), labels,
                                                  sizes, **kw)
    root = reference.root_from_gradients(
        run.model, run.gen.column(run.model["tree_info"][0]["tree_structure"]
                                  ["split_feature"], 0, run.rows),
        g, h, lambda_l2=float(run.params.get("lambda_l2", 0.0)))
    # (c): the state the window left
    score = np.asarray(run.booster._gbdt.train_score.score[0])
    g_now, h_now = program_gradients(run.booster, score)
    g_ref_now, h_ref_now = reference.lambdarank_gradients(
        score[at].astype(np.float64), labels, sizes, **kw)
    stretches = walk_stretches(run.gen.seed, run.rows, run.gen.block_rows,
                               WALK_BLOCKS, WALK_ROWS)
    with concurrent.futures.ThreadPoolExecutor(WALK_BLOCKS) as pool:
        walk = np.concatenate(list(pool.map(
            lambda r: reference.raw_scores(run.model, run.gen.rows(*r)[0]),
            stretches)))
    walked = np.concatenate([score[lo:hi] for lo, hi in stretches])
    compared = {
        "grad_err": (worst(g[at], g_ref), GRAD_TOL),
        "hess_err": (worst(h[at], h_ref), GRAD_TOL),
        "root_left_count_err": (root["left_count_err"],
                                ROOT_COUNT_TOL if run.rows > 1 << 24 else 0.0),
        "root_gain_rel_err": (root["gain_rel_err"], ROOT_GAIN_RTOL),
        "score_walk_err": (float(np.abs(walked - walk).max()),
                           SCORE_WALK_TOL * max(1.0, float(
                               np.abs(walk).max()))),
        "grad_err_trained": (worst(g_now[at], g_ref_now), GRAD_TOL),
        "hess_err_trained": (worst(h_now[at], h_ref_now), GRAD_TOL),
    }
    # not a condition of `correct`: which queries the fused kernel took is
    # the program's choice (those over `tpu_rank_tile` go to its bucketed
    # path), and the gradients of either path are held to the reference
    off_kernel = int(obj.rank_fused_fallback_queries if obj.rank_fused_active
                     else len(groups))
    return compared, dict(root, grad_queries=len(picked), grad_rows=len(at),
                          grad_first_query=int(picked[0]),
                          grad_last_query=int(picked[-1]),
                          walk_stretches=stretches,
                          rank_queries_off_kernel=off_kernel,
                          largest_gradient=float(np.abs(g_ref).max()),
                          largest_gradient_trained=float(
                              np.abs(g_ref_now).max()))
