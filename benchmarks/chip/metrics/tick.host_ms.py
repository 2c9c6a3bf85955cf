"""Host time per task in the scheduler tick: the ticks' wall time in the
window minus the program's own ``s_per_step`` (step dispatch to the synced
loss) of the window's tasks, divided by those tasks."""


def reduce(run):
    t0, t1 = run.window
    ticks = run.spans.within("tick", t0, t1)
    if not run.tasks or not ticks:
        return None
    return 1e3 * (sum(ticks) - sum(run.s_per_step)) / run.tasks
