"""device.idle_share: percent of the traced window in which a device ran no
operation (1 - union of its 'XLA Ops' intervals / window), averaged over the
cell's devices, from the profiler trace."""


def read(ctx):
    trace = ctx["trace"]
    if trace is None:
        return None
    return 100.0 * trace["idle_share"]
