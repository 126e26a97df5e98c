"""Engine steps per dispatched burst in the window (engine loop): short
bursts mean a host round trip every few steps."""


def read(run):
    ds = [d for d in run.dispatches if run.in_window(d.t)]
    return sum(d.steps for d in ds) / len(ds) if ds else None
