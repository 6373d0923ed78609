"""Nearest-neighbour descent baseline (Dong et al., 2011).

Greedy iterative KNN-graph refinement: candidates are generated by the
local join (neighbours-of-neighbours, forward and reverse edges) with no
embedding feedback and no random probes -- exactly the property that traps
it in local minima on well-separated clusters (paper Fig. 7 'Disjointed').

Shares the fixed-shape merge machinery with FUnc-SNE's iterative KNN so the
comparison isolates the *candidate generation policy*.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core import knn as knn_lib
from repro.kernels.knn_merge.ops import knn_merge
from repro.kernels.pairwise_sqdist.ops import pairwise_sqdist_gather


@dataclasses.dataclass(frozen=True)
class NNDConfig:
    """Candidates come from the counter-based hash RNG (funcsne §Perf
    H17) and are generated, scored, dedup'd and merged by one
    ``knn_merge`` launch (§H16); the cached reverse edges ride in as
    "extra" slots."""
    k: int = 32
    c_fwd: int = 8         # forward neighbours-of-neighbours per iter
    c_rev: int = 4         # reverse-edge hops per iter
    backend: str = "auto"
    # refresh cadence of the reverse-edge table in the ``nnd`` driver:
    # the argsort over all n*K directed edges rebuilds every
    # rev_refresh iterations instead of every iteration.  Default 1 ==
    # the per-iteration rebuild, bit-for-bit: NND is a *baseline* whose
    # tol-based termination is sensitive to stale reverse edges, so
    # amortisation is opt-in here (funcsne defaults to 10).
    rev_refresh: int = 1


def nnd_init(rng, X, cfg: NNDConfig):
    n = X.shape[0]
    ids = jnp.arange(n, dtype=jnp.int32)
    idx = knn_lib.init_knn_idx(rng, n, n, cfg.k)
    d = pairwise_sqdist_gather(X, ids, idx, backend=cfg.backend)
    order = jnp.argsort(d, axis=1)
    return (jnp.take_along_axis(idx, order, axis=1),
            jnp.take_along_axis(d, order, axis=1))


def _reverse_table(idx, rng, cfg: NNDConfig):
    """(n, c_rev) sampled reverse edges, padded from the counter fill
    keyed on ``split(rng)[1]`` -- the one protocol both the in-step and
    the cached rebuild use."""
    n = idx.shape[0]
    fill = knn_lib.counter_fill(knn_lib.key_salt(jax.random.split(rng)[1]),
                                n, cfg.c_rev)
    return knn_lib.reverse_neighbors(idx, n, cfg.c_rev, fill=fill)


def nnd_step(rng, X, idx, d, cfg: NNDConfig, rev=None):
    """One NND iteration; returns (idx, d, update_fraction).

    ``rev`` is the cached (n, c_rev) reverse-edge table; ``None`` rebuilds
    it in-step (:func:`_reverse_table`), which the :func:`nnd` driver
    reproduces bit-for-bit at ``rev_refresh=1``.
    """
    n = X.shape[0]
    ids = jnp.arange(n, dtype=jnp.int32)
    if cfg.c_rev and rev is None:
        rev = _reverse_table(idx, rng, cfg)
    # forward hops + rev-of-fwd hops (closing the local join); the cached
    # reverse edges ride in as "extra" slots
    salt = knn_lib.key_salt(jax.random.split(rng)[0])
    sources = (("two_hop", 0, 0, cfg.c_fwd),)
    firsts = (idx,)
    if cfg.c_rev:
        sources += (("extra", cfg.c_rev), ("two_hop", 1, 0, cfg.c_rev))
        firsts += (rev,)
    idx, d, improved = knn_merge(X, ids, idx, d, rev if cfg.c_rev else None,
                                 backend=cfg.backend, sources=sources,
                                 salt=salt, first_tables=firsts,
                                 second_tables=(idx,))
    return idx, d, jnp.mean(improved.astype(jnp.float32))


def nnd(X, cfg: NNDConfig = NNDConfig(), rng=None, max_iter: int = 40,
        tol: float = 1e-3):
    """Run NND to convergence (update fraction < tol) or max_iter.

    The reverse-edge table is cached across iterations and rebuilt every
    ``cfg.rev_refresh`` steps (the argsort over all n*K directed edges
    was the only per-iteration O(E log E) term left) with the same
    protocol as the in-step rebuild.  ``rev_refresh=1`` (the default)
    runs the in-step rebuild program itself, so caching is a
    ``rev_refresh>1`` opt-in.
    """
    X = jnp.asarray(X, jnp.float32)
    if rng is None:
        rng = jax.random.PRNGKey(0)
    idx, d = nnd_init(rng, X, cfg)
    cache = cfg.c_rev > 0 and cfg.rev_refresh > 1
    step = jax.jit(lambda r, i, dd, rv: nnd_step(r, X, i, dd, cfg, rev=rv))
    rebuild = jax.jit(lambda i, r: _reverse_table(i, r, cfg))
    history = []
    rev = None
    for it in range(max_iter):
        r_it = jax.random.fold_in(rng, it)
        if cache and it % cfg.rev_refresh == 0:
            rev = rebuild(idx, r_it)
        idx, d, frac = step(r_it, idx, d, rev)
        history.append(float(frac))
        if history[-1] < tol:
            break
    return idx, d, history
