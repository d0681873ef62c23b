"""The ODS sampler's choice of each batch's ids (the span ``sample``
around ``session.next_batch_ids()`` in the device route, with its
residency and in-flight pushes) per batch served in the window, in
ms."""

KEYS = ("sample",)


def read(run):
    before, after = run.times_before, run.times_after
    n = after.get("batches", 0) - before.get("batches", 0)
    if n <= 0 or not all(k in after for k in KEYS):
        return None
    return 1e3 * sum(after[k] - before[k] for k in KEYS) / n
