"""The image feed's wait for a built batch (the span ``wait`` in
``DSIPipeline.get``, on the caller's thread, while the prefetch thread
builds ahead) per batch taken in the window, in ms."""


def read(run):
    before, after = run.times_before, run.times_after
    n = after.get("taken", 0) - before.get("taken", 0)
    if n <= 0 or "wait" not in after:
        return None
    return 1e3 * (after["wait"] - before["wait"]) / n
