"""Device time of LD refinement per iteration of the batch window, in
milliseconds: the union of the op events under the ``funcsne.ld_refine``
scope, outside the window's ``bench.init`` span, over the iterations
(``bench/phases.py``)."""
from bench import phases


def read(run):
    evs = phases.run_events(run, "batch",
                            holes=run.trace.spans_named("init"))
    return phases.per_iteration_ms(evs, "ld_refine", run.iterations)
