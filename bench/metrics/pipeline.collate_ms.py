"""The device route's own collate timer (``DSIPipeline.times.collate``)
per batch served in the window, in ms.  The pipeline does not block on
the stacked batch before it stops this timer, so it times the dispatch
of the stack, not the copy."""


def read(run):
    n = run.times_after["batches"] - run.times_before["batches"]
    if n <= 0:
        return None
    return 1e3 * (run.times_after["collate"] - run.times_before["collate"]) / n
