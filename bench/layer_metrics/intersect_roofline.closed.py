"""Kernel: the tier program's share of its HBM roofline, in %.

Device time: the XLA modules whose name holds ``_tier_intersect`` in the
traced window.  Least bytes: ``yardstick.work.intersect_bytes`` over the
queries answered in the window that reach the device, at the device's
HBM bandwidth from ``yardstick/peaks.json``."""
from bench.yardstick import trace_reduce, work


def read(run):
    if run.trace is None or run.peaks is None or run.traced_pairs is None:
        return None
    seconds, calls = trace_reduce.total_time(run.trace.modules, run.trace_lo, run.trace_hi,
                                             "_tier_intersect")
    if not calls or seconds <= 0:
        return None
    lab = run.labels
    u, v = run.traced_pairs[:, 0], run.traced_pairs[:, 1]
    dev = work.needs_labels(u, v, lab["out_len"], lab["in_len"], lab["level"])
    nbytes = work.intersect_bytes(lab["out_len"], lab["in_len"], u[dev], v[dev],
                                  lab["n_hops"], lab["n"])
    return 100.0 * nbytes / run.peaks["hbm_bytes_per_s"] / seconds
