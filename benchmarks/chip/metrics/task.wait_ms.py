"""Time a task waits between its claim and its step: the start of its
``wf.dispatch`` span minus the end of the ``wf.claim`` span that claimed it
(matched by task id), averaged over the tasks of the window that the
program's tracer recorded."""
import progspans


def reduce(run):
    return progspans.task_wait_ms(progspans.spans(run))
