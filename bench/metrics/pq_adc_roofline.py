"""Share of its roofline the pq_adc kernel reached in the traced window:
least time at the chip's peaks for the operations and bytes its launches
need (harness.counts.pq_adc), over their device time."""
from harness import counts, trace as tr

KERNELS = ("adc_distances_kernel",)


def read(ctx):
    if ctx.events is None or ctx.peaks is None:
        return None
    least = spent = 0.0
    for ev in tr.matching(ctx.events, KERNELS):
        try:
            flops, nbytes = counts.pq_adc(
                tr.operand_shapes(tr.long_name(ev)), m=ctx.config["pq_m"],
                ksub=ctx.config["pq_ksub"])
        except (IndexError, ValueError):
            continue        # not a launch whose shapes the count reads
        least += counts.roofline_seconds(flops, nbytes, ctx.peaks)[0]
        spent += ev.dur_ns / 1e9
    return 100.0 * least / spent if spent else None
