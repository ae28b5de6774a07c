"""Admission and windows: queries per dispatch window, from the service's
own counters (``ServiceStats.jobs_run`` over ``ServiceStats.batches``)
across the measured window and its drain."""


def read(run):
    jobs = sum(s.jobs_run for s in run.window.steps)
    batches = sum(s.batches for s in run.window.steps)
    return jobs / batches if batches else None
