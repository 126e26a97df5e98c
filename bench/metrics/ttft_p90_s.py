"""90th percentile of time to first token over the requests that arrived in
the window: the first token's delivery to the client (a streaming burst
boundary) minus the time the request was due.  A request with no token by
the end of the drain counts with the time it had waited then."""
import numpy as np


def read(run):
    xs = [(r.first if r.first is not None else run.stop) - r.arrival
          for r in run.counted]
    return float(np.percentile(xs, 90)) if xs else None
