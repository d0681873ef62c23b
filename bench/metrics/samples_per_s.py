"""Training samples whose step completed in the window, over the window."""


def read(run):
    return run.samples / run.window_s
