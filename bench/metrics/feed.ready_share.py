"""The share of the batches the image feed took in the window that the
prefetch thread had already built when asked for (``DSIPipeline.get``'s
counts ``ready`` over ``taken``), in %."""


def read(run):
    before, after = run.times_before, run.times_after
    n = after.get("taken", 0) - before.get("taken", 0)
    if n <= 0 or "ready" not in after:
        return None
    return 100.0 * (after["ready"] - before["ready"]) / n
