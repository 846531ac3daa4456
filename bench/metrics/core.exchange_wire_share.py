"""Device time of the halo exchange on the wire over the device's busy
time, both summed over the cell's chips.

The exchange's ppermutes run as asynchronous collective-permutes, whose
ops the trace names ``collective-permute-start`` and
``collective-permute-done``; every op whose name starts with
``collective-permute`` is counted.  The 1-bit pack and unpack around them
are fusions the trace cannot tell from the sweep's own glue by name, so
this share is the wire alone, not the whole exchange.  None where no such
op ran: a cell whose lattice lies in one brick has no collective."""

PREFIX = "collective-permute"


def read(ctx):
    kernels = ctx.get("kernels")
    if not kernels or ctx.get("busy_total_s", 0) <= 0:
        return None
    ns = sum(s for name, (_, s) in kernels.items()
             if name.startswith(PREFIX))
    if ns <= 0:
        return None
    return 100.0 * (ns / 1e9) / ctx["busy_total_s"]
