"""Names of the program's device scopes and host spans, in one place.

Device scopes (``jax.named_scope``) add only HLO metadata: each compiled
instruction's ``op_name`` carries the scopes it was traced under, forward
(``jvp(...)``), backward (``transpose(jvp(...))``) and inside a scan's body.
Host spans (``jax.profiler.TraceAnnotation``) are events of the profiler's
host plane, on the same clock as the device's operations; with no profiler
session they cost about a microsecond each.
"""

# Device scopes, outermost first where they nest.
RNN_SCAN = "rnn.scan"    # each lax.scan over time: carries, stores, read-out
DCGRU = "dcgru"          # one DCGRU cell: stacks, projections, gates
DCONV_HOP = "dconv.hop"  # one diffusion hop Z_k = S Z_{k-1}
GATHER = "gather"        # the train/eval step's window gather
OPTIMIZER = "optimizer"  # the schedule, clipping and Adam

DEVICE_SCOPES = (GATHER, RNN_SCAN, DCGRU, DCONV_HOP, OPTIMIZER)

# Host spans.
FIT_START = "fit.start"          # Engine.fit's entry to the first step
FIT_END = "fit.end"              # the last step to Engine.fit's return
STEP_DISPATCH = "step.dispatch"  # the call of the jitted train step
FEED_NEXT = "feed.next"          # one step's batch
FEED_WAIT = "feed.wait"          # a get from the prefetcher's stage queue
FEED_TRANSFER = "feed.transfer"  # starts to the device
FEED_BUILD = "feed.build"        # the prefetcher's thread building a block
LOOP_LOG_SYNC = "loop.log_sync"  # reading a logging step's metrics
LOOP_CKPT = "loop.ckpt"          # a checkpoint save
LOOP_HEALTH = "loop.health"      # the health poll
LOOP_EVAL = "loop.eval"          # the epoch-end evaluation

HOST_SPANS = (FIT_START, FIT_END, STEP_DISPATCH, FEED_NEXT, FEED_WAIT,
              FEED_TRANSFER, FEED_BUILD, LOOP_LOG_SYNC, LOOP_CKPT, LOOP_HEALTH,
              LOOP_EVAL)
