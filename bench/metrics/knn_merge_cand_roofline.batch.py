"""Roofline share of the candidate-fused merge kernel (HD and LD calls
together), in percent: the least time the chip needs for the calls'
operations or bytes (bench/roofline/knn_merge_cand.py), whichever bound
is larger, over the kernel's device time.  LD refinement launches once
per iteration; the remaining launches are HD refinements."""
from bench import trace as trace_lib


def read(run):
    count, busy = run.trace.kernel(0, "knn_merge_cand")
    seconds = trace_lib.length(busy) * 1e-9
    hd_calls = count - run.iterations
    if not count or seconds <= 0 or hd_calls < 0:
        return None
    model = run.roofline("knn_merge_cand")
    fs = run.config["funcsne"]
    hd_ops, hd_bytes = model.hd(run.config, fs)
    ld_ops, ld_bytes = model.ld(run.config, fs)
    return trace_lib.roofline_share(
        seconds, hd_calls * hd_ops + run.iterations * ld_ops,
        hd_calls * hd_bytes + run.iterations * ld_bytes, run.peaks)
