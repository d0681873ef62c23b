"""The VLM feed's captions (the span ``text`` in
``launch/train.image_batch_source``: each sample's caption ids and
next-token labels, and their upload) per batch served in the window, in
ms."""

KEYS = ("text",)


def read(run):
    before, after = run.times_before, run.times_after
    n = after.get("batches", 0) - before.get("batches", 0)
    if n <= 0 or not all(k in after for k in KEYS):
        return None
    return 1e3 * sum(after[k] - before[k] for k in KEYS) / n
