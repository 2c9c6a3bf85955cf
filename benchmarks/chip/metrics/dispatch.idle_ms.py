"""Device idle time at step launch: the holes in chip 0's ``XLA Ops`` union
in the traced part that the program's ``wf.batch`` and ``wf.dispatch``
spans cover, per step dispatched there."""
import progspans


def reduce(run):
    return progspans.dispatch_idle_ms(run, progspans.spans(run))
