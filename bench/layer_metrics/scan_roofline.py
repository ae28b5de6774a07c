"""Kernel and the operations around it: the share of its roofline the
scan reaches.  The least time the chip could take for the windows traced
is, window by window, the larger of the bytes the filter semantics need
over the HBM bandwidth and their operations over the bf16 peak
(``bench.measure.step_work``: valid ``pt`` values, counts, ids and the
scalar columns read; never the kernel's padded tile).  That least time
is divided by the device-busy time inside the traced ``step()`` and
``drain`` spans, whatever operations filled it: no kernel name is
looked up, so a later kernel is measured on the same work."""
from bench import measure


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    busy = run.trace.busy_within(("step", "drain"))
    least = 0.0
    for step in run.window.steps:
        nbytes, flops = measure.step_work(step, run.window, run.family,
                                          run.shape, run.calib_iters)
        least += max(nbytes / run.peaks["hbm_bytes_per_s"],
                     flops / run.peaks["bf16_flops_per_s"])
    if busy <= 0 or least <= 0:
        return None
    return 100.0 * least / busy
