"""Roofline share of the edge-mode force kernel, in percent: the least
time the chip needs for its operations or bytes
(bench/roofline/ne_forces_gather.py), whichever bound is larger, over
the kernel's device time."""
from bench import trace as trace_lib


def read(run):
    count, busy = run.trace.kernel(0, "ne_forces_gather")
    seconds = trace_lib.length(busy) * 1e-9
    if not count or seconds <= 0:
        return None
    ops, nbytes = run.roofline("ne_forces_gather").edge_mode(
        run.config, run.config["funcsne"])
    return trace_lib.roofline_share(seconds, count * ops, count * nbytes,
                                    run.peaks)
