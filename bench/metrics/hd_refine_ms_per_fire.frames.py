"""Device time of HD refinement per frame whose gate fired, in
milliseconds: the union of the window's op events under the
``funcsne.hd_refine`` scope, over the ``bench.frame`` spans in which
one of them starts (``bench/phases.py``)."""
from bench import phases


def read(run):
    return phases.per_holding_span_ms(phases.run_events(run, "frames"),
                                      run.trace.spans_named("frame"),
                                      "hd_refine")
