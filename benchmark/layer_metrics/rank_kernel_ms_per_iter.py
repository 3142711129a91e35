"""Device milliseconds per iteration inside Mosaic kernels that are not
the aligned engine's (`ops/aligned.py` names its three): on a ranking run
that is the fused lambdarank gradient kernel (`ops/pallas_rank.py`), under
whatever name the trace gives it. Averaged over the chips; None where no
such kernel ran."""

ALIGNED = ("move_pass", "slot_hist_pass", "count_pass")


def others(trace) -> set:
    """Names of the traced kernels that are none of the engine's."""
    return {k for k in trace.get("kernels", ()) if k not in ALIGNED}


def read(ctx):
    ops, names = ctx["trace"]["ops"], others(ctx["trace"])
    ns = sum(e - s for ev in ops.values() for n, s, e in ev if n in names)
    if not ns:
        return None
    return ns / len(ops) / 1e6 / ctx["iterations"]
