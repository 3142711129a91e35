"""Device milliseconds per iteration inside the Pallas kernels of
`ops/aligned.py` (move, slot-histogram and count passes together: the
breakdown names each), averaged over the chips."""


def read(ctx):
    ops = ctx["trace"]["ops"]
    if not ops:
        return None
    kernels = ctx["trace"]["kernels"]
    ns = sum(e - s for ev in ops.values() for n, s, e in ev if n in kernels)
    return ns / len(ops) / 1e6 / ctx["iterations"]
