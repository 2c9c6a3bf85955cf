"""Host time of one ``claim_all`` call (one per tick) in the window."""


def reduce(run):
    t0, t1 = run.window
    d = run.spans.within("claim", t0, t1)
    return 1e3 * sum(d) / len(d) if d else None
