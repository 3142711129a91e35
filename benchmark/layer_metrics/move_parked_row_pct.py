"""Of the training rows, the share that lay OUTSIDE the rounds of the
window's trees: 100 x `rows_parked` summed over the window's
`aligned.iter` records over `aligned.pack`'s `rows` x iterations. An
engine that parks the rows its bag leaves out (the partition by the bag
lane, the tree's rounds over the in-bag chunks, one `walk_pass` a tree
for the parked rows) reads 100 less the bag's share: 70.0 under GOSS at
0.2 / 0.1, 20.0 under `bagging_fraction=0.8`; one that moves every row
through every round has no such counter. None, and never an error, where
no iteration of the window carries it (the parent of the PR that added
it; an engine that is not bagged)."""
from benchmark.layer_metrics import _seams


def read(ctx):
    recs = _seams.ring()
    win = _seams.window(recs, ctx["iterations"])
    pack = _seams.named(recs, "aligned.pack")
    if win is None or not pack or not pack[-1].get("rows"):
        return None
    parked = [r["rows_parked"] for r in win["iters"] if "rows_parked" in r]
    if len(parked) != len(win["iters"]):
        return None
    return 100.0 * sum(parked) / len(parked) / pack[-1]["rows"]
