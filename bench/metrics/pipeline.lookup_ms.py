"""Every tiered cache lookup of the device route, hit or miss (the
counter ``lookup`` of ``DSIPipeline.times``, summed over the
``lookup_tiered`` calls of each batch) per batch served in the window,
in ms."""

KEYS = ("lookup",)


def read(run):
    before, after = run.times_before, run.times_after
    n = after.get("batches", 0) - before.get("batches", 0)
    if n <= 0 or not all(k in after for k in KEYS):
        return None
    return 1e3 * sum(after[k] - before[k] for k in KEYS) / n
