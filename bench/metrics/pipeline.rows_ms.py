"""Slot bookkeeping after each group's device output (the span ``rows``:
the group's slots noted beside its output, which stays whole, and each
of its rows listed for admission, whose slices ``admit_rows`` then
cuts) per batch served in the window, in ms."""

KEYS = ("rows",)


def read(run):
    before, after = run.times_before, run.times_after
    n = after.get("batches", 0) - before.get("batches", 0)
    if n <= 0 or not all(k in after for k in KEYS):
        return None
    return 1e3 * sum(after[k] - before[k] for k in KEYS) / n
