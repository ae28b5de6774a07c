"""Admission and windows: 95th percentile, over the tickets a window
served, of the wait from a ticket's due instant (open loop) or its
submission (closed loop) to the start of the ``step()`` that served it.
Read from the harness's own records on the host clock."""
import numpy as np


def read(run):
    per = {t.ticket: t for t in run.window.tickets}
    waits = [s.t_start - per[tid].t_start
             for s in run.window.steps for tid in s.tickets if tid in per]
    return float(np.percentile(waits, 95)) if waits else None
