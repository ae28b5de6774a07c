"""``bench/sweep.py``'s verdict on one window: a queue that moves with the
phase of its windows is sustained; one that grows, or a window that
meets the cap, is not."""
from bench import drive, sweep


def window(due, starts, step_s=2.0, per_step=None, take=None,
           seconds=40.0):
    """Tickets due at ``due``, served oldest first, at most ``take`` by
    each step; steps start at ``starts`` and last ``step_s``."""
    steps, tickets = [], []
    for i, t in enumerate(due):
        tickets.append(drive.TicketRecord(i, None, t, t))
    for s in starts:
        served = [r.ticket for r in tickets
                  if r.t_start <= s and not any(r.ticket in x.tickets
                                                for x in steps)][:take]
        steps.append(drive.StepRecord(served, "step", s, s + step_s,
                                      per_step or len(served), 1, 0))
    return drive.WindowRecord("open", seconds, tickets, steps,
                              starts[-1] + step_s)


def test_a_steady_queue_is_sustained():
    due = [i * 0.1 for i in range(400)]              # 10 per second
    starts = [2.0 * k for k in range(1, 21)]
    v = sweep.judge(window(due, starts), 40.0, 64, 10.0)
    assert v["sustained"] and v["capped_windows"] == 0


def test_a_growing_queue_is_not():
    due = [i * 0.1 for i in range(400)]
    # each window serves 15 of the 20 that arrive during it
    starts = [2.0 * k for k in range(1, 21)]
    v = sweep.judge(window(due, starts, take=15), 40.0, 64, 10.0)
    assert not v["sustained"] and v["capped_windows"] == 0


def test_a_window_at_the_cap_is_not():
    due = [i * 0.1 for i in range(400)]
    starts = [2.0 * k for k in range(1, 21)]
    v = sweep.judge(window(due, starts, per_step=64), 40.0, 64, 10.0)
    assert not v["sustained"] and v["capped_windows"] == 20
