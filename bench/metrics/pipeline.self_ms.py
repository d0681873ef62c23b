"""The device route's ``next_batch`` less its eight child spans
(``sample``, ``gather``, ``fused``, ``augment``, ``rows``,
``admit_rows``, ``collate``, ``upkeep``): the time no span of the route
covers, per batch served in the window, in ms."""

CHILDREN = ("sample", "gather", "fused", "augment", "rows", "admit_rows",
            "collate", "upkeep")


def read(run):
    before, after = run.times_before, run.times_after
    n = after.get("batches", 0) - before.get("batches", 0)
    if n <= 0 or not all(k in after for k in ("next_batch",) + CHILDREN):
        return None
    total = after["next_batch"] - before["next_batch"]
    spans = sum(after[k] - before[k] for k in CHILDREN)
    return 1e3 * (total - spans) / n
