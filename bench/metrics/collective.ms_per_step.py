"""collective.ms_per_step: milliseconds per traced step in which the first
device ran a collective (the union of its all-gather, all-reduce,
reduce-scatter, all-to-all and collective-permute intervals, synchronous and
asynchronous), from the profiler trace (``bench/trace_reduce.py``).  None
where the trace holds no collective."""


def read(ctx):
    trace = ctx["trace"]
    if trace is None or not trace["has_collectives"]:
        return None
    return 1e3 * trace["collective_s_per_step"]
