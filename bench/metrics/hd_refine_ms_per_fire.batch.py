"""Device time of HD refinement per fire of its gate in the batch window,
in milliseconds: the union of the op events under the
``funcsne.hd_refine`` scope, outside the window's ``bench.init`` span,
over the number of separate runs of that phase, one per step whose gate
fired (``bench/phases.py``)."""
from bench import phases


def read(run):
    evs = phases.run_events(run, "batch",
                            holes=run.trace.spans_named("init"))
    return phases.per_run_ms(evs, "hd_refine")
