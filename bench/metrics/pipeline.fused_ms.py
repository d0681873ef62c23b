"""The fused decode+augment call on the host's clock, from its host
parameters to its output ready on the device (the span ``fused``
around ``decode_augment_batch_seeded``), per batch served in the
window, in ms."""

KEYS = ("fused",)


def read(run):
    before, after = run.times_before, run.times_after
    n = after.get("batches", 0) - before.get("batches", 0)
    if n <= 0 or not all(k in after for k in KEYS):
        return None
    return 1e3 * sum(after[k] - before[k] for k in KEYS) / n
