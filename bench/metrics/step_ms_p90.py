"""90th percentile over the window's steps of the wall time from one
step's completion to the next (data wait included), in ms."""
import numpy as np


def read(run):
    return float(np.percentile(run.step_intervals, 90)) * 1e3
