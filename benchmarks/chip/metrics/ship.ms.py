"""Producer-thread time of one replication ship (``sync_replicas``, once per
steering tick) in the window."""


def reduce(run):
    t0, t1 = run.window
    d = run.spans.within("ship", t0, t1)
    return 1e3 * sum(d) / len(d) if d else None
