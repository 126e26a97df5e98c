"""90th percentile of admission minus arrival over the requests that
arrived in the window (driver + batcher).  A request never admitted counts
with its wait at the end of the run."""
import numpy as np


def read(run):
    xs = [(r.admitted if r.admitted is not None else run.stop) - r.arrival
          for r in run.counted]
    return float(np.percentile(xs, 90)) if xs else None
