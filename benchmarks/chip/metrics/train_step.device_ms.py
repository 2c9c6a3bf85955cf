"""Device time of one jitted train step (``jit_step`` in the trace's
``XLA Modules`` line), averaged over the steps of the traced window."""
import devtrace


def reduce(run):
    if run.trace is None:
        return None
    times = devtrace.module_times(run.trace, "jit_step")
    return 1e3 * sum(times) / len(times) if times else None
