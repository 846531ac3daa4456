"""Share of the traced window in which no operation ran on the device,
averaged over the chips the cell uses (1 - busy union / window)."""


def read(ctx):
    if "busy_s" not in ctx or ctx["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - ctx["busy_s"] / ctx["window_s"])
