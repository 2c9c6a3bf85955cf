"""Time the analyst thread spent on one sweep, over the sweeps handed to it
in the window."""


def reduce(run):
    d = [s["end"] - s["start"] for s in run.sweeps
         if "end" in s and "start" in s]
    return 1e3 * sum(d) / len(d) if d else None
