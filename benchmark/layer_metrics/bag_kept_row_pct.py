"""Of the training rows, the share in the bag, over the window's
iterations: 100 x `bag_kept` / rows, from the counter that rides each
`aligned.iter` record (the device's own count of the lane it wrote at the
last re-bag). `bagging_fraction=0.8` gives 80.0 exactly. None where no
iteration of the window carries the counter."""
from benchmark.layer_metrics import _seams


def read(ctx):
    recs = _seams.ring()
    win = _seams.window(recs, ctx["iterations"])
    pack = _seams.named(recs, "aligned.pack")
    if win is None or not pack:
        return None
    kept = [r["bag_kept"] for r in win["iters"] if "bag_kept" in r]
    if not kept:
        return None
    return 100.0 * sum(kept) / len(kept) / pack[-1]["rows"]
