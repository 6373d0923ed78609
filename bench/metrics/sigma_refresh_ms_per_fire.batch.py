"""Device time of the bandwidth (sigma) refresh per refresh in the batch
window, in milliseconds: the union of the op events under the
``funcsne.sigma_refresh`` scope, outside the window's ``bench.init``
span, over the number of separate runs of that phase, one per step on
the refresh cadence with a row flagged (``bench/phases.py``)."""
from bench import phases


def read(run):
    evs = phases.run_events(run, "batch",
                            holes=run.trace.spans_named("init"))
    return phases.per_run_ms(evs, "sigma_refresh")
