"""Share of its roofline that the fused decode+augment kernel reaches,
in %: the bytes each call must move (``decode_augment_bytes``, its
output written once in its own dtype and five int32 scalars a sample
read) at the chip's HBM bandwidth, over the kernel's device time in the
trace.  The bound is memory bandwidth: the kernel's work is integer
hashing on the vector unit, for which the v5e publishes no peak, so its
compute bound cannot be stated."""
from benchlib import yardstick

PROGRAM = "decode_augment"


def read(run):
    if run.trace is None:
        return None
    calls, secs = run.trace.module_time(PROGRAM)
    if calls == 0 or secs <= 0:
        return None
    d = run.cell.traffic["dataset"]
    need = calls * yardstick.decode_augment_bytes(run.batch, d["crop_hw"])
    return 100.0 * need / run.peaks["hbm_bytes_per_s"] / secs
