"""The device route's own fetch timer (``DSIPipeline.times.fetch``:
lookups, storage fetches and cache reads) per batch served in the
window, in ms."""


def read(run):
    n = run.times_after["batches"] - run.times_before["batches"]
    if n <= 0:
        return None
    return 1e3 * (run.times_after["fetch"] - run.times_before["fetch"]) / n
