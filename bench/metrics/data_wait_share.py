"""Share of the window the train loop spent in ``next_batch`` (host
spans of the harness around each call), in %."""


def read(run):
    wait = sum(b - a for name, a, b in run.spans if name == "next_batch")
    return 100.0 * wait / run.window_s
