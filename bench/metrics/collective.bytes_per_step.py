"""collective.bytes_per_step: GiB per device that the compiled train step's
collectives return (all-gather, all-reduce, reduce-scatter, all-to-all,
collective-permute; result shapes, an async pair counted once), read from the
step's compiled text by ``bench/hlo.py``.  None where the step has none."""

BYTES_PER_GIB = 2**30


def read(ctx):
    total = ctx["collectives"]["total"]
    if not total:
        return None
    return total / BYTES_PER_GIB
