"""Scan: events swept per second of ``step()``, from the service's own
``ServiceStats.events_scanned`` over the summed wall time of the steps."""


def read(run):
    events = sum(s.events_scanned for s in run.window.steps)
    wall = sum(s.t_end - s.t_start for s in run.window.steps)
    return events / wall if events and wall > 0 else None
