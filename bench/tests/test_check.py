"""The output check refuses its control and every fault the cells can
have, and passes the program, at a size a CPU test run holds."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, common, generator, run
from bench.reference import lists as lists_ref
from bench.reference import sigma as sigma_ref
from bench.reference import step as step_ref
from repro.core import funcsne

TINY = {"atlas-262k.batch": 1024, "mnist-70k.frames": 1000}
SEED = 2 ** 31 + 777


def _run(capsys, workload, *extra):
    rc = run.main(["--workload", workload, "--seed", str(SEED),
                   "--seconds", "1.5", "--trace", "0", *extra],
                  require_tpu=False,
                  overrides={"config": {"n": TINY[workload]}})
    out = capsys.readouterr()
    assert rc == 0, out.err[-2000:]
    return json.loads(out.out.strip().splitlines()[-1]), out.err


@pytest.mark.parametrize("workload", sorted(TINY))
def test_control_fails_and_program_passes(capsys, workload):
    line, err = _run(capsys, workload, "--control", "1")
    assert line["correct"] is True, line["checks"]
    control = next(json.loads(s.split("control ", 1)[1])
                   for s in err.splitlines()
                   if s.startswith("bench: control "))
    numbers = {k: v["value"] for k, v in line["checks"].items()}
    numbers.update(control)
    ok, table = check.judge(numbers, common.find_cell(workload)["limits"])
    assert not ok, table


def _unchanged(new, old):
    return old


def _rows_from_old(*fields):
    """The step's output with the named fields of half the rows kept
    from its input."""
    def fault(new, old):
        h = old.Y.shape[0] // 2
        return new._replace(**{f: getattr(new, f).at[h:].set(
            getattr(old, f)[h:]) for f in fields})
    return fault


def _answer_altered(new, old):
    n = new.hd_idx.shape[0]
    return new._replace(hd_idx=new.hd_idx.at[0, 0].set(
        (new.hd_idx[0, 0] + 7) % n))


def _perplexity_ignored(hp):
    return hp._replace(perplexity=jnp.float32(10.0))


# name: (fault on the step's output, fault on its hyperparameters)
FAULTS = {
    "unchanged": (_unchanged, None),
    "half_left_out": (_rows_from_old("Y", "vel"), None),
    "answer_altered": (_answer_altered, None),
    "hd_refine_half_skipped": (_rows_from_old("hd_idx", "hd_d"), None),
    "ld_refine_half_skipped": (_rows_from_old("ld_idx", "ld_d"), None),
    "sigma_half_skipped": (_rows_from_old("beta"), None),
    "perplexity_ignored": (None, _perplexity_ignored),
}


def _broken(make, out_fault, hp_fault):
    """A program factory whose steps go through the faults."""
    def factory(*args, **kwargs):
        real = make(*args, **kwargs)

        def prog(st, X, hp):
            old = jax.tree.map(jnp.copy, st)
            out = real(st, X, hp_fault(hp) if hp_fault else hp)
            if out_fault is None:
                return out
            if isinstance(out, tuple) and not hasattr(out, "_fields"):
                return (out_fault(out[0], old),) + tuple(out[1:])
            return out_fault(out, old)
        prog.lower = real.lower
        return prog
    return factory


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("workload", sorted(TINY))
def test_faults_make_correct_false(monkeypatch, capsys, workload, fault):
    for name in ("make_chunked_step", "make_step"):
        monkeypatch.setattr(funcsne, name,
                            _broken(getattr(funcsne, name), *FAULTS[fault]))
    line, _ = _run(capsys, workload)
    assert line["correct"] is False, line["checks"]


def test_reference_rng_matches_program():
    from bench.reference import rng
    from repro.core import knn
    key = jax.random.PRNGKey(2 ** 31 + 5)
    salt = knn.hash3(knn.key_salt(key), 17, rng.TAG_NEG)
    rows = jnp.arange(64, dtype=jnp.int32)[:, None]
    draws = jnp.arange(16, dtype=jnp.int32)[None, :]
    want = np.asarray(knn.counter_randint(salt, rows, draws, 1000))
    ref_salt = rng.hash3(rng.key_salt(np.asarray(key)), 17, rng.TAG_NEG)
    got = rng.counter_randint(ref_salt, np.asarray(rows), np.asarray(draws),
                              1000)
    assert np.array_equal(got, want)
    assert rng.counter_uniform01(rng.hash3(ref_salt, 3, 1)) == \
        float(knn.counter_uniform01(knn.hash3(salt, 3, 1)))


def test_reference_candidates_match_program():
    from bench.reference import lists, rng
    from repro.core import knn
    n, k = 300, 8
    r = np.random.default_rng(3)
    first = r.integers(0, n, (n, k)).astype(np.int32)
    second = r.integers(0, n, (n, k)).astype(np.int32)
    fs = {"c_hd_non": 4, "c_hd_ld": 2, "c_hd_ld_non": 2, "c_hd_rand": 2}
    sources = lists.hd_sources(fs)
    salt = knn.hash3(knn.key_salt(jax.random.PRNGKey(9)), 5, rng.TAG_HD)
    want = knn.counter_candidates(salt, jnp.arange(n, dtype=jnp.int32),
                                  sources, (first, second), (first, second),
                                  n_total=n)
    ref_salt = rng.hash3(rng.key_salt(np.asarray(jax.random.PRNGKey(9))),
                         5, rng.TAG_HD)
    got = rng.candidates(ref_salt, sources, (first, second),
                         (first, second), n)
    assert np.array_equal(got, np.asarray(want))


def test_sigma_reference_hits_its_target():
    from bench.reference import sigma
    d2 = np.random.default_rng(1).gamma(2.0, 3.0, (64, 32))
    beta = sigma.solve(d2, 15.0)
    assert np.allclose(sigma.entropy(d2, beta), np.log(15.0), atol=1e-9)
    # above the list's length: uniform weights
    assert np.all(sigma.solve(d2, 40.0) == 0.0)
    assert np.allclose(sigma.target(d2, 40.0), np.log(32.0))


def _synthetic(n: int = 3072, seed: int = 11):
    """(pre, posts, X, fs) of a check at a refresh step: two phases whose
    lists, distances, bandwidths and velocities are the reference's own
    in float32 (so the gaps are at rounding), with faults planted in
    three rows: an HD entry and an LD entry that are no candidates, and
    an embedding entry that is not Y + vel."""
    fs = common.load_json(common.BENCH / "configs" / "atlas-262k.json")[
        "funcsne"]
    r, r64 = np.random.default_rng(seed), step_ref.rounder("float64")
    f32 = np.float32
    X = r.standard_normal((n, 50)).astype(f32)
    Y = r.standard_normal((n, 2)).astype(f32)

    def distinct(k):
        """k different ids per row, none the row itself."""
        base = r.choice(n - 1, k, replace=False)
        shift = r.integers(0, n - 1, (n, 1))
        return ((np.arange(n)[:, None] + 1 + (base + shift) % (n - 1))
                % n).astype(np.int32)

    pre = {"Y": Y, "vel": (0.01 * r.standard_normal((n, 2))).astype(f32),
           "gains": np.ones((n, 2), f32), "zhat": f32(1e5),
           "step": np.int32(40), "hd_idx": distinct(fs["k_hd"]),
           "hd_d": np.zeros((n, fs["k_hd"]), f32),
           "ld_idx": distinct(fs["k_ld"]),
           "beta": np.full(n, 0.5, f32), "new_flag": np.ones(n, np.int32),
           "active": np.ones(n, bool), "ema_new_frac": f32(1.0),
           "key": np.array([0, 2 ** 31 + 5], np.uint32)}
    hd_union = lists_ref.hd_union(pre, fs)
    hd_idx = lists_ref.best(hd_union,
                            lists_ref.union_sqdist(X, hd_union, r64),
                            fs["k_hd"])
    ld_union = lists_ref.ld_union(pre, {"hd_idx": hd_idx}, fs)
    ld_idx = lists_ref.best(ld_union,
                            lists_ref.union_sqdist(Y, ld_union, r64),
                            fs["k_ld"])
    # no candidate of their row
    hd_idx[3, 0] = np.setdiff1d(np.arange(4, n), hd_union[3])[0]
    ld_idx[11, 2] = np.setdiff1d(np.arange(12, n), ld_union[11])[0]
    hd_idx[3] = hd_idx[3][np.argsort(step_ref.sqdist(X, hd_idx, r64)[3],
                                     kind="stable")]
    hd_d = step_ref.sqdist(X, hd_idx, r64)
    lists = {"hd_idx": hd_idx.astype(np.int32),
             "hd_d": hd_d.astype(f32), "ld_idx": ld_idx.astype(np.int32),
             "ld_d": step_ref.sqdist(Y, ld_idx, r64).astype(f32)}
    base = generator.base_hparams(n, {})
    posts = []
    for hp in (step_ref.schedule(base, 40, 750),
               dict(base, perplexity=f32(12.0))):
        post = dict(lists, beta=sigma_ref.solve(
            hd_d, hp["perplexity"]).astype(f32))
        post["vel"] = step_ref.one_step(pre, post, hd_d, hp, fs)[
            "vel"].astype(f32)
        post["Y"] = (Y + post["vel"]).astype(f32)
        posts.append((post, hp))
    posts[0][0]["Y"][5, 0] += f32(1.0)
    return pre, posts, X, fs


def _numbers(pre, posts, X, fs):
    """The check's numbers and information, its control's numbers, and
    the arrays of the reference they come from: the control's outputs
    (bfloat16) and the float64 step and bandwidths of the first phase."""
    ctl = check.control_posts(pre, posts, X, fs)
    post, hp = posts[0]
    ref = check.Reference(pre, post, X, fs)
    arrays = [a for c, _ in ctl for a in c.values()]
    arrays += list(step_ref.one_step(pre, post, ref.hd_d, hp, fs).values())
    arrays += [ref.beta(hp["perplexity"]), ref.d_hd_union, ref.d_ld_union]
    return (check.readings(pre, posts, X, fs),
            check.readings(pre, ctl, X, fs)[0], arrays)


@pytest.fixture(scope="module")
def whole_array():
    """The synthetic state and its numbers with one block that holds
    every row on one thread: the computation of a plain loop-free
    reference."""
    state = _synthetic()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(step_ref, "ROW_BLOCK", len(state[2]))
        mp.setattr(step_ref, "threads", lambda: 1)
        return state, _numbers(*state)


@pytest.mark.parametrize("block", [512, 700, None])
@pytest.mark.parametrize("pool", [1, 8])
def test_blocks_and_threads_give_the_same_numbers(monkeypatch, whole_array,
                                                  pool, block):
    """The reference in row blocks (512 divides n, 700 does not; None is
    one block of every row) on a pool of 1 or 8 threads gives the
    numbers of one whole-array block, bit for bit."""
    state, (want, want_ctl, want_arrays) = whole_array
    numbers = want[0]
    assert numbers["hd_merge_faults"] > 0 and numbers["ld_merge_faults"] > 0
    assert numbers["update_faults"] > 0 and numbers["list_faults"] == 0
    assert 0 < numbers["force_err"] < 1e-5
    monkeypatch.setattr(step_ref, "ROW_BLOCK", block or len(state[2]))
    monkeypatch.setattr(step_ref, "threads", lambda: pool)
    got, got_ctl, got_arrays = _numbers(*state)
    assert got == want
    assert got_ctl == want_ctl
    for a, b in zip(got_arrays, want_arrays, strict=True):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype and np.array_equal(a, b)


def test_pool_is_capped(monkeypatch):
    monkeypatch.setattr(os, "cpu_count", lambda: 10 ** 6)
    assert step_ref.threads() == step_ref.MAX_THREADS == 32
    monkeypatch.setattr(os, "cpu_count", lambda: None)
    assert step_ref.threads() == 1
