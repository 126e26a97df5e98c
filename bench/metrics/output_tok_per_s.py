"""Output tokens delivered to clients over the time they took, between
the first and the last iteration end inside the window: every token the
iterations after the first delivered, over all the time between those
two ends.  Tokens reach the client only at iteration ends, which a
backlog spaces a whole burst apart (~2.4 s for minicpm_2b.decode), so a
count over the fixed window would move only in steps of a burst."""


def read(run):
    ends = [t for t in run.iteration_ends if run.in_window(t)]
    if len(ends) < 2:
        return None
    b0, b1 = ends[0], ends[-1]
    return sum(n for t, n in run.deliveries if b0 < t <= b1) / (b1 - b0)
