"""Mean over the window's loop iterations of the KV pool's leased but
unwritten token capacity over the arena's capacity, in percent (KV pool):
reservations of prompt + output tokens that hold blocks before they are
written."""


def read(run):
    xs = [s for t, s in run.kv_unused if run.in_window(t)]
    return sum(xs) / len(xs) * 100.0 if xs else None
