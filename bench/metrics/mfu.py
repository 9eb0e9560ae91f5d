"""mfu: the whole step's share of the chip's peak: analytic backbone FLOPs
of the rows computed in the window (bench/flops; padding rows and the
want pass not counted) over the window's seconds times the device's bf16
peak (bench/peaks.json), in percent."""


def read(run):
    rows = sum(t.rows for t in run.ticks)
    if not rows:
        return None
    return 100.0 * run.flops_per_row * rows / (run.window_s * run.peak_flops)
