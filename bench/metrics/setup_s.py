"""Seconds from process start to the start of the lead-in: imports, weights
made on the device, the serving loop built, every burst program compiled
or loaded, and one warm request per slot."""


def read(run):
    return run.setup_s
