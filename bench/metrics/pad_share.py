"""Scheduler pad waste (``QueryScheduler.stats``): pad slots over all
slots of the batches completed in the window, in %."""


def read(run):
    s = run["sched"]
    slots = s["answered"] + s["padded"]
    return 100.0 * s["padded"] / slots if slots else None
