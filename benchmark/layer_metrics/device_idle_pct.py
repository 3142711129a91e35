"""Share of the traced window in which no operation ran on the chip:
100 x (1 - union of the device operations' intervals / window)."""


def read(ctx):
    w = ctx["trace"]["window"]
    if not ctx["trace"]["ops"] or w["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - w["busy_s"] / w["window_s"])
