"""The whole train step's share of the chips' bf16 peak: the analytic FLOPs
of the tasks committed in the traced window over that window's length."""


def reduce(run):
    if run.trace is None or not run.tasks_traced:
        return None
    lo, hi = run.trace.window
    return 100.0 * run.flops_per_task * run.tasks_traced / (
        (hi - lo) * run.chips * run.peaks["bf16_flops_per_s"])
