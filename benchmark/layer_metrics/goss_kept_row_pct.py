"""Of the training rows, the share that the device's selection kept, over
the window's iterations: 100 x (`goss_kept_top` + `goss_kept_other`) /
rows, from the counters that ride each `aligned.iter` record. top_rate +
other_rate = 30.0 but for rows that tie with the threshold, which are all
kept. None where no iteration of the window carries the counters."""
from benchmark.layer_metrics import _seams


def read(ctx):
    recs = _seams.ring()
    win = _seams.window(recs, ctx["iterations"])
    pack = _seams.named(recs, "aligned.pack")
    if win is None or not pack:
        return None
    kept = [r["goss_kept_top"] + r["goss_kept_other"]
            for r in win["iters"] if "goss_kept_top" in r]
    if not kept:
        return None
    return 100.0 * sum(kept) / len(kept) / pack[-1]["rows"]
