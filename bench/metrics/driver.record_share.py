"""Device time of the energy readout (``brick_energy``) over the device's
busy time: what the recorded driver's record points cost."""


def read(ctx):
    kernels = ctx.get("kernels")
    if not kernels or ctx.get("busy_total_s", 0) <= 0:
        return None
    ns = sum(s for name, (_, s) in kernels.items()
             if ctx["energy_kernel"] in name)
    if ns <= 0:
        return None
    return 100.0 * (ns / 1e9) / ctx["busy_total_s"]
