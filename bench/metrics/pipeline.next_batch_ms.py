"""The device route's whole ``next_batch`` (the span ``next_batch`` of
``DSIPipeline.times`` around ``_next_batch_device``) per batch served in
the window, in ms.  Nothing to read where the pipeline has no such
span."""

KEYS = ("next_batch",)


def read(run):
    before, after = run.times_before, run.times_after
    n = after.get("batches", 0) - before.get("batches", 0)
    if n <= 0 or not all(k in after for k in KEYS):
        return None
    return 1e3 * sum(after[k] - before[k] for k in KEYS) / n
