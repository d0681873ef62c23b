"""Splitting each group's device output into one row a sample (the span
``rows``: ``out[i]`` for every slot, kept for the batch and for
admission) per batch served in the window, in ms."""

KEYS = ("rows",)


def read(run):
    before, after = run.times_before, run.times_after
    n = after.get("batches", 0) - before.get("batches", 0)
    if n <= 0 or not all(k in after for k in KEYS):
        return None
    return 1e3 * sum(after[k] - before[k] for k in KEYS) / n
