"""Edge and global aggregation's share of its roofline: the least time of
the window's warm HieAvg mixes (``work.aggregate_work``, bytes-bound) over
the device time of the ops that do them: the fused ``hieavg_agg`` and
``coef_agg`` kernels, or the XLA aggregation of ``core.hieavg`` that
stands in for them.  Moves ``samples_per_s``."""

FRAMES = [("kernels/hieavg_agg.py", "*"),
          ("kernels/coef_agg.py", "*"),
          ("core/hieavg.py", "*"),
          ("kernels/dispatch.py", "edge_aggregate_batched"),
          ("kernels/dispatch.py", "global_aggregate"),
          ("kernels/dispatch.py", "edge_aggregate_cold_batched"),
          ("kernels/dispatch.py", "global_aggregate_cold"),
          ("kernels/ops.py", "fused_mix_and_update"),
          ("kernels/ops.py", "fused_edge_aggregate_batched"),
          ("kernels/ops.py", "fused_coef_aggregate")]
OP_NAMES = ["hieavg_agg", "coef_agg"]


def read(run):
    t = run.trace.attributed_s(FRAMES, OP_NAMES)
    if t <= 0:
        return None
    least, _ = run.least_time(run.work["agg_flops"], run.work["agg_bytes"])
    return 100.0 * least / t
