"""Model FLOPs of the samples whose step completed in the window
(forward and backward, no recomputation) over the window times the
chip's bf16 peak, in %."""


def read(run):
    return 100.0 * run.flops_per_sample * run.samples / (
        run.window_s * run.peaks["bf16_flops"])
