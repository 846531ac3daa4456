"""The sweep kernels' share of their roofline: the least time the bytes
each launch must move take at the chip's HBM bandwidth, over the kernels'
device time.  Bytes per launch come from the shapes (``kernel_bytes``),
launches and time from the trace.  The roofline is the memory bound
alone: the kernels are VPU integer work, and no VPU integer peak is
published for the chip, so the compute bound cannot be taken."""


def read(ctx):
    kernels = ctx.get("kernels")
    if not kernels:
        return None
    nbytes = secs = 0.0
    for name, per_launch in ctx["sweep_kernels"].items():
        n, ns = kernels.get(name, (0, 0.0))
        nbytes += n * per_launch
        secs += ns / 1e9
    if secs <= 0:
        return None
    return 100.0 * nbytes / secs / ctx["peaks"]["hbm_bytes_per_s"]
