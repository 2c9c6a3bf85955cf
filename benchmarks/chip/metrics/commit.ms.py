"""Host time of one task's commit (``WorkQueue.finish`` with its ``TxnLog``
record) in the window."""


def reduce(run):
    t0, t1 = run.window
    d = run.spans.within("commit", t0, t1)
    return 1e3 * sum(d) / len(d) if d else None
