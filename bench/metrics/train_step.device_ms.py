"""Device time of the compiled train step per step in the window, from
the trace's program events, in ms."""

PROGRAM = "jit_step"


def read(run):
    if run.trace is None:
        return None
    calls, secs = run.trace.module_time(PROGRAM)
    if calls == 0:
        return None
    return 1e3 * secs / calls
