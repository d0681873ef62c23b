"""Cache admission on the device route: the per-sample encoded
admissions of misses (the counter ``admit``) and the admission of the
freshly augmented rows (the span ``admit_rows``: votes and
``admit_batch``), per batch served in the window, in ms."""

KEYS = ("admit", "admit_rows")


def read(run):
    before, after = run.times_before, run.times_after
    n = after.get("batches", 0) - before.get("batches", 0)
    if n <= 0 or not all(k in after for k in KEYS):
        return None
    return 1e3 * sum(after[k] - before[k] for k in KEYS) / n
