"""The output check that decides ``correct``.

After the window the harness runs the window's own compiled program on
from its end state to the next step that refreshes the bandwidths
(``sigma_refresh_every``), marks every row as having new neighbours and
the recent improvement share as 1, so that the next step refines the HD
lists and re-solves every row's bandwidth, and copies that state to the
host (``pre``).  It then makes that one step with each set of
hyperparameters the cell's traffic uses (the batch schedule's, or each
drag phase's) and copies each result (``post``).  The numbers compared:

- ``list_faults``: entries of the HD and LD lists out of range, equal to
  their own row, repeated within a row; HD rows not sorted by distance;
  non-finite HD distances; lists that differ between the phases' steps,
  which refine alike (limit 0).
- ``hd_merge_faults``, ``ld_merge_faults``: entries of the new lists that
  are neither in the old list nor among the step's valid candidates,
  plus entries of those that were left out while the list keeps a
  farther one; the candidates and the HD gate are redrawn from the
  program's counters (``reference/lists.py``; limit 0).
- ``update_faults``: non-finite embedding entries, and entries where the
  new embedding is not the old one plus the new velocity to rounding
  (limit 0).
- ``hd_d_err``, ``ld_d_err``: the largest gap between the distances the
  refinement phases stored and the reference's float64 distances of the
  same pairs (HD against the data, LD against the embedding they were
  scored on), over max(reference, median reference distance).
- ``sigma_err``: the largest gap between the entropy of a refreshed
  row's affinities at the program's bandwidth and its target
  (``reference/sigma.py``), over the target.  Rows whose reference
  bandwidth lies more than ``WARM_START`` octaves from the bandwidth the
  solve starts from are left out: the program's warm-started bisection
  reaches them over several refreshes, not in one (their share is
  reported beside the numbers).
- ``force_err``: the largest gap between the program's new velocity and
  the reference step's, each entry over the magnitude of what it sums
  (|momentum * old velocity| plus lr * gains * 4 * the absolute force
  contributions): attraction and repulsion nearly cancel in a settled
  map, so the net update is no yardstick of rounding.  The reference's
  attraction uses the program's bandwidths, which ``sigma_err`` checks.

``control_posts`` puts the reference, computed in bfloat16, in the
program's place: the check must refuse it.

The reference works on blocks of rows on a pool of threads
(``reference/step.py``); every reduction over rows is one call over all
of them, so the numbers do not depend on the blocks or the pool.
``readings`` adds the wall time of each of its parts to ``seconds``.
"""
from __future__ import annotations

import contextlib
import time

import numpy as np

from bench.reference import lists as lists_ref
from bench.reference import sigma as sigma_ref
from bench.reference import step as step_ref

STATE_PRE = ("Y", "vel", "gains", "zhat", "step", "hd_idx", "hd_d",
             "ld_idx", "beta", "new_flag", "active", "ema_new_frac")
STATE_POST = ("Y", "vel", "hd_idx", "hd_d", "ld_idx", "ld_d", "beta")
# |Y' - (Y + vel')| allowed, in units of float32 spacing of the operands
UPDATE_ULPS = 4.0
# octaves between the bandwidth a refresh starts from and the reference's
# within which one refresh must land on the target
WARM_START = 12.0


@contextlib.contextmanager
def timed(seconds: dict, part: str):
    """Add the wall time of the ``with`` block to ``seconds[part]``."""
    t = time.perf_counter()
    try:
        yield
    finally:
        seconds[part] = seconds.get(part, 0.0) + time.perf_counter() - t


def host_state(jax, st, fields) -> dict:
    """Copy the named fields of a FuncSNEState (plus its raw key) to the
    host."""
    out = dict(zip(fields, jax.device_get([getattr(st, f) for f in fields])))
    key = st.rng
    if jax.dtypes.issubdtype(key.dtype, jax.dtypes.prng_key):
        key = jax.random.key_data(key)
    out["key"] = np.asarray(jax.device_get(key))
    return {k: np.asarray(v) for k, v in out.items()}


def _list_faults(idx, n) -> int:
    def block(s, e):
        rows = idx[s:e]
        bad = (rows < 0) | (rows >= n) | (rows == np.arange(s, e)[:, None])
        srt = np.sort(rows, axis=1)
        return int(bad.sum() + np.sum(srt[:, 1:] == srt[:, :-1]))

    return sum(step_ref.by_rows(block, idx.shape[0]))


def _rel_gap(got, ref, mag=None) -> float:
    """max |got - ref| / max(mag, median mag); ``mag`` defaults to the
    reference itself."""
    mag = np.abs(ref) if mag is None else mag
    median = np.median(mag)

    def block(s, e):
        scale = np.maximum(mag[s:e], median)
        scale = np.where(scale > 0, scale, 1.0)
        return np.max(np.abs(np.asarray(got[s:e], np.float64) - ref[s:e])
                      / scale)

    gap = float(np.max(step_ref.by_rows(block, len(ref))))
    return gap if np.isfinite(gap) else float("inf")


def _update_faults(pre: dict, post: dict) -> int:
    Y0, Y1, v1 = (np.asarray(a, np.float64)
                  for a in (pre["Y"], post["Y"], post["vel"]))
    spacing = np.spacing(np.maximum(np.abs(Y0), np.abs(Y1)).astype(
        np.float32)).astype(np.float64)
    bad = int(np.sum(~np.isfinite(Y1)) + np.sum(~np.isfinite(v1)))
    return bad + int(np.sum(np.abs(Y1 - (Y0 + v1)) > UPDATE_ULPS * spacing
                            + np.spacing(np.abs(v1).astype(np.float32))))


class Reference:
    """What the reference derives once from ``pre`` and the first step,
    shared by every phase's step and by the control."""

    def __init__(self, pre: dict, post0: dict, X, fs: dict,
                 seconds: dict | None = None):
        self.pre, self.fs = pre, fs
        seconds = {} if seconds is None else seconds
        self.X = np.asarray(X, np.float32)
        n = self.X.shape[0]
        r64 = step_ref.rounder("float64")
        with timed(seconds, "unions"):
            self.hd_union = lists_ref.hd_union(pre, fs)
            self.ld_union = lists_ref.ld_union(pre, post0, fs)
        with timed(seconds, "union_sqdist"):
            self.d_hd_union = lists_ref.union_sqdist(self.X, self.hd_union,
                                                     r64)
            self.d_ld_union = lists_ref.union_sqdist(pre["Y"],
                                                     self.ld_union, r64)
        with timed(seconds, "hd_d"):
            self.hd_d = step_ref.sqdist(self.X, np.clip(
                post0["hd_idx"].astype(np.int64), 0, n - 1), r64)
        # rows the step re-solves: all flagged ones, at a refresh step
        changed = np.any(post0["hd_idx"] != pre["hd_idx"], axis=1)
        flagged = pre["new_flag"].astype(bool) | changed
        refresh = int(pre["step"]) % int(fs["sigma_refresh_every"]) == 0
        self.refreshed = flagged if refresh and flagged.any() \
            else np.zeros(n, bool)
        self._beta = {}

    def beta(self, perplexity) -> np.ndarray:
        """The reference's bandwidths at ``perplexity`` (solved once)."""
        key = float(perplexity)
        if key not in self._beta:
            self._beta[key] = sigma_ref.solve(self.hd_d, perplexity)
        return self._beta[key]

    def sigma(self, post: dict, perplexity) -> tuple[float, float]:
        """(sigma_err, share of refreshed rows left out as too far from
        their warm start)."""
        rows = self.refreshed
        beta, beta0 = post["beta"], self.pre["beta"]
        if np.any(beta[~rows] != beta0[~rows]):
            return float("inf"), 0.0
        if not rows.any():
            return 0.0, 0.0
        star = self.beta(perplexity)[rows]
        with np.errstate(divide="ignore"):
            octaves = np.abs(np.log2(star / beta0[rows].astype(np.float64)))
        near = (star == 0) | (octaves <= WARM_START)
        d2 = self.hd_d[rows][near]
        t = sigma_ref.target(d2, perplexity)
        b = beta[rows][near].astype(np.float64)
        h = np.concatenate(step_ref.by_rows(
            lambda s, e: sigma_ref.entropy(d2[s:e], b[s:e]), len(d2)))
        gap = float(np.max(np.abs(h - t) / t)) if near.any() else 0.0
        return (gap if np.isfinite(gap) else float("inf"),
                float(1.0 - near.mean()))


def readings(pre: dict, posts: list, X, fs: dict,
             seconds: dict | None = None) -> tuple[dict, dict]:
    """(the compared numbers, information beside them) of the steps
    ``pre -> post`` for each ``(post, hp)`` in ``posts``; the seconds of
    each part are added to ``seconds``."""
    seconds = {} if seconds is None else seconds
    ref = Reference(pre, posts[0][0], X, fs, seconds)
    n = ref.X.shape[0]
    post0 = posts[0][0]
    hd_idx = post0["hd_idx"].astype(np.int64)
    ld_idx = post0["ld_idx"].astype(np.int64)
    hd_d = post0["hd_d"].astype(np.float64)

    with timed(seconds, "lists"):
        lists = _list_faults(hd_idx, n) + _list_faults(ld_idx, n)
        lists += int(np.sum(~np.isfinite(hd_d)))
        lists += int(np.sum(np.diff(hd_d, axis=1) < 0))
        for post, _ in posts[1:]:
            lists += int(np.sum(post["hd_idx"] != post0["hd_idx"]))
            lists += int(np.sum(post["ld_idx"] != post0["ld_idx"]))
    with timed(seconds, "merge_faults"):
        hd_merge = lists_ref.merge_faults(hd_idx, ref.hd_union,
                                          ref.d_hd_union)
        ld_merge = lists_ref.merge_faults(ld_idx, ref.ld_union,
                                          ref.d_ld_union)
    with timed(seconds, "distances"):
        r64 = step_ref.rounder("float64")
        ref_ld = step_ref.sqdist(pre["Y"], np.clip(ld_idx, 0, n - 1), r64)
        hd_d_err = _rel_gap(hd_d, ref.hd_d)
        ld_d_err = _rel_gap(post0["ld_d"], ref_ld)
    update, force, sigma, far = 0, 0.0, 0.0, 0.0
    for post, hp in posts:
        with timed(seconds, "one_step"):
            update += _update_faults(pre, post)
            step = step_ref.one_step(pre, post, ref.hd_d, hp, fs)
            force = max(force, _rel_gap(post["vel"], step["vel"],
                                        step["scale"]))
        with timed(seconds, "sigma"):
            gap, share = ref.sigma(post, hp["perplexity"])
        sigma, far = max(sigma, gap), max(far, share)
    numbers = {
        "list_faults": lists,
        "hd_merge_faults": hd_merge,
        "ld_merge_faults": ld_merge,
        "update_faults": update,
        "hd_d_err": hd_d_err,
        "ld_d_err": ld_d_err,
        "sigma_err": sigma,
        "force_err": force}
    info = {"step": int(pre["step"]),
            "hd_refined": bool(lists_ref.gate_fires(pre, fs)),
            "rows_resolved": int(ref.refreshed.sum()),
            "sigma_far_share": far}
    return numbers, info


def control_posts(pre: dict, posts: list, X, fs: dict) -> list:
    """``posts`` with everything the reference can produce replaced by the
    reference computed in bfloat16: the new lists and their distances,
    the bandwidths, the new velocity and the new embedding."""
    ref = Reference(pre, posts[0][0], X, fs)
    rb = step_ref.rounder("bfloat16")
    k_hd, k_ld = pre["hd_idx"].shape[1], pre["ld_idx"].shape[1]
    hd_idx = lists_ref.best(ref.hd_union, rb(ref.d_hd_union), k_hd)
    ld_idx = lists_ref.best(ref.ld_union, rb(ref.d_ld_union), k_ld)
    hd_d = step_ref.sqdist(ref.X, hd_idx, rb)
    ld_d = step_ref.sqdist(pre["Y"], ld_idx, rb)
    out = []
    for post, hp in posts:
        ctl = dict(post, hd_idx=hd_idx, hd_d=hd_d, ld_idx=ld_idx, ld_d=ld_d)
        beta = pre["beta"].astype(np.float64).copy()
        rows = ref.refreshed
        beta[rows] = sigma_ref.solve(hd_d[rows], hp["perplexity"], rb)
        ctl["beta"] = beta
        step = step_ref.one_step(pre, ctl, hd_d, hp, fs,
                                 precision="bfloat16")
        ctl["vel"], ctl["Y"] = step["vel"], step["Y"]
        out.append((ctl, hp))
    return out


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """(all within limits, {name: {"value", "limit"}}); a number with no
    limit, or a limit with no number, fails."""
    out, ok = {}, True
    for name in sorted(set(numbers) | set(limits)):
        value, limit = numbers.get(name), limits.get(name)
        good = value is not None and limit is not None and value <= limit
        ok &= bool(good)
        out[name] = {"value": value, "limit": limit}
    return ok, out
