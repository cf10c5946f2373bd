"""Share of its roofline the robust_prune kernel (flush, merge Delete,
Insert and Patch launches) reached in the traced window
(harness.counts.robust_prune over device time)."""
from harness import counts, trace as tr

KERNELS = ("robust_prune_fp_kernel", "delete_repair_fp_kernel")


def read(ctx):
    if ctx.events is None or ctx.peaks is None:
        return None
    least = spent = 0.0
    for ev in tr.matching(ctx.events, KERNELS):
        try:
            flops, nbytes = counts.robust_prune(
                tr.operand_shapes(tr.long_name(ev)), R=ctx.config["R"])
        except (IndexError, ValueError):
            continue        # not a launch whose shapes the count reads
        least += counts.roofline_seconds(flops, nbytes, ctx.peaks)[0]
        spent += ev.dur_ns / 1e9
    return 100.0 * least / spent if spent else None
