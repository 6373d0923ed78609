"""What the program records about itself: the gate counters in
:class:`ChunkMetrics` and the ``funcsne.*`` host spans of the chunk loop.

The phase scopes (``funcsne.hd_refine`` ...) are checked on the program
compiled for a described v5e in tests/test_tpu_compile.py, where the
Mosaic kernels exist.
"""
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import funcsne
from repro.core.resilience import ResiliencePolicy
from repro.data.synthetic import blobs

SPANS = ("chunk", "dispatch", "sync", "snapshots", "audit", "checkpoint")


def _setup(n=96, dim=9, seed=0):
    X, _ = blobs(n=n, dim=dim, n_centers=3, center_std=5.0, seed=seed)
    Xj = jnp.asarray(X)
    cfg = funcsne.FuncSNEConfig(n_points=n, dim_hd=dim, backend="xla")
    st0 = funcsne.init_state(jax.random.PRNGKey(seed), Xj, cfg)
    return cfg, st0, Xj, funcsne.default_hparams(n)


def _copy(st):
    return jax.tree.map(lambda a: jnp.array(a, copy=True), st)


def test_chunk_counts_gate_fires_of_the_single_step_path():
    """A 12-step chunk counts exactly the ``do_hd`` / ``do_sigma`` flags of
    12 single steps from the same state (steps 0 and 10 are on the sigma
    cadence, so the window holds a refresh), and ends in the same state
    bit for bit."""
    cfg, st0, Xj, hp = _setup()
    T = 12
    step = jax.jit(lambda s, x, h: funcsne._step_flags(cfg, s, x, h,
                                                       funcsne.AxisCtx()))
    st, n_hd, n_sigma = _copy(st0), 0, 0
    for _ in range(T):
        st, do_hd, do_sigma = step(st, Xj, hp)
        n_hd += int(do_hd)
        n_sigma += int(do_sigma)
    assert n_sigma >= 1 and n_hd >= 1

    st_c, _, m = funcsne.make_chunked_step(cfg, T)(_copy(st0), Xj, hp)
    assert (int(m.hd_fires), int(m.sigma_fires)) == (n_hd, n_sigma)
    assert m.hd_fires.dtype == m.sigma_fires.dtype == jnp.int32
    for name in funcsne.FuncSNEState._fields:
        np.testing.assert_array_equal(np.asarray(getattr(st_c, name)),
                                      np.asarray(getattr(st, name)),
                                      err_msg=name)


def _host_spans(trace_dir):
    """(name, start_ns, end_ns) of every ``funcsne.*`` host span."""
    (path,) = Path(trace_dir).rglob("*.xplane.pb")
    pd = jax.profiler.ProfileData.from_file(str(path))
    return [(e.name, e.start_ns, e.end_ns)
            for plane in pd.planes if plane.name.startswith("/host")
            for line in plane.lines for e in line.events
            if e.name.startswith("funcsne.")]


def _fit(loop, X, cfg, n_iter, chunk_size, tmp_path):
    policy = ResiliencePolicy(checkpoint_dir=str(tmp_path / "ck"),
                              audit_every=1)
    if loop == "fit":
        return funcsne.fit(X, cfg=cfg, n_iter=n_iter, chunk_size=chunk_size,
                           snapshot_every=5, resilience=policy)[0]
    from repro.runtime.coordinator import fit_elastic
    return fit_elastic(X, cfg=cfg, n_iter=n_iter, chunk_size=chunk_size,
                       devices=jax.devices()[:1], resilience=policy)


@pytest.mark.parametrize("loop", ["fit", "fit_elastic"])
def test_fit_records_one_span_set_per_chunk(tmp_path, loop):
    """Under ``jax.profiler.trace`` a fit of ``n_iter`` iterations records
    ceil(n_iter / chunk_size) ``funcsne.chunk`` spans, each holding one of
    every other span its loop opens (this policy syncs, audits and
    checkpoints every chunk; ``fit`` also drains snapshots): nothing is
    recorded per step."""
    n_iter, chunk_size = 25, 10
    X, _ = blobs(n=64, dim=6, n_centers=2, center_std=5.0, seed=4)
    cfg = funcsne.FuncSNEConfig(n_points=64, dim_hd=6, backend="xla")
    with jax.profiler.trace(str(tmp_path / "trace")):
        st = _fit(loop, X, cfg, n_iter, chunk_size, tmp_path)
        jax.block_until_ready(st.Y)
    spans = _host_spans(tmp_path / "trace")
    chunks = [(s, e) for n, s, e in spans if n == "funcsne.chunk"]
    assert len(chunks) == math.ceil(n_iter / chunk_size) == 3
    want = set(SPANS) - ({"snapshots"} if loop == "fit_elastic" else set())
    assert {n for n, _, _ in spans} == {"funcsne." + s for s in want}
    for name in want - {"chunk"}:
        inside = [sum(s <= a and b <= e for n, a, b in spans
                      if n == "funcsne." + name) for s, e in chunks]
        assert inside == [1] * len(chunks), (name, inside)
    assert len(spans) == len(want) * len(chunks)
