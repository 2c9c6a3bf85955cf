"""Share of the traced window in which no operation ran on the device
(1 minus the union of the ``XLA Ops`` intervals), averaged over chips."""
import devtrace


def reduce(run):
    if run.trace is None:
        return None
    busy, window = devtrace.busy_and_window(run.trace)
    return 100.0 * (1.0 - busy / window)
