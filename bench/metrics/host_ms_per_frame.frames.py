"""Host time per frame: each frame span's wall time minus the device-busy
time inside it (dispatch, hyperparameter upload, embedding readback and
recall probe on the host), mean over the frames, in milliseconds."""
from bench import trace as trace_lib


def read(run):
    frames = run.trace.spans_named("frame")
    if not frames:
        return None
    busy = run.trace.busy(0)
    host = [(e - s) - trace_lib.overlap(busy, s, e) for s, e in frames]
    return 1e-6 * sum(host) / len(host)
