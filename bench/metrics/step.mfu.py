"""step.mfu: the whole train step's share of the chips' peak, in percent.

Useful FLOPs per window, as the configuration's family counts them
(``window_flops`` of ``bench/reference/<family>.py``; for DCGRU models
diffusion hops on each support's nonzeros, backward 1x for hops and 2x for
projections, remat not counted), times the measured windows per second, over
chips times the peak FLOP/s of the run's ``device_kind``
(``bench/peaks.json``)."""


def read(ctx):
    return (100.0 * ctx["useful_flops"] * ctx["windows_per_s"]
            / (ctx["chips"] * ctx["peak_flops_per_s"]))
