"""90th percentile, over the requests that arrived in the window, of the
time per output token after the first: (done - first token) / (tokens - 1),
in milliseconds.  A request unfinished at the end of the drain counts with
the time it had taken then per token delivered."""
import numpy as np


def read(run):
    xs = []
    for r in run.counted:
        if r.first is None:
            continue
        end = r.done if r.done is not None else run.stop
        xs.append((end - r.first) / max(len(r.tokens) - 1, 1) * 1e3)
    return float(np.percentile(xs, 90)) if xs else None
