"""Device time per iteration outside the Mosaic kernels: the chunk
program's XLA glue (sorts, scatters, sigma solve, update), i.e. busy
time minus the kernels' union, outside the window's ``bench.init`` span
(the initial state), over the iterations in the window, in
milliseconds."""


def read(run):
    if not run.iterations:
        return None
    return 1e3 * run.summary["outside_kernels_s"] / run.iterations
