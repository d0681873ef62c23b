"""Seconds from process start to the window: imports, building the
system, filling the tier, compiling or loading the programs and the
first steps."""


def read(run):
    return run.setup_s
