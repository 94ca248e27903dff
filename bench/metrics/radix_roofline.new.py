"""Share of the bandwidth roof that the radix planner's digit-pass
kernels reach, in %: their computed bytes (``kernel_bytes``, a lower
bound: keys read, positions written) over their device time, over the
chip's HBM bandwidth."""

from bench import kernel_bytes, tracereduce

#: the HLO names of the two Pallas kernels of one digit pass, as the
#: trace's op events carry them (``digit_block_histogram.3``)
HISTOGRAM = ("digit_block_histogram",)
PLACEMENT = ("digit_placement",)


def read(ctx):
    lo, hi = ctx.window()
    hist = tracereduce.kernel_ops(ctx.trace.ops, HISTOGRAM[0], lo, hi)
    place = tracereduce.kernel_ops(ctx.trace.ops, PLACEMENT[0], lo, hi)
    t = tracereduce.busy(hist + place, lo, hi)
    if t == 0:
        return None
    L = int(ctx.cfg["L"])
    moved = (len(hist) * kernel_bytes.radix_histogram_bytes(L)
             + len(place) * kernel_bytes.radix_placement_bytes(L))
    return 100.0 * moved / (t / 1e9) / ctx.peak["hbm_bytes_s"]
