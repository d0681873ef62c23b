"""The image feed's step input (the span ``patchify`` in
``launch/train.image_batch_source``: ``patchify_stub`` and the labels)
per batch served in the window, in ms."""

KEYS = ("patchify",)


def read(run):
    before, after = run.times_before, run.times_after
    n = after.get("batches", 0) - before.get("batches", 0)
    if n <= 0 or not all(k in after for k in KEYS):
        return None
    return 1e3 * sum(after[k] - before[k] for k in KEYS) / n
