"""train_mfu: model FLOPs of the rows trained in the window (forward and
backward at the published widths, no recompute) over the window seconds
times the bf16 peak of the device kind, in percent."""


def read(run):
    if not run.rows or not run.peak_flops:
        return None
    return 100.0 * run.rows * run.flops_per_sample / (
        run.window_s * run.peak_flops)
