"""The output check refuses its control and every fault the cells can
have, and passes the program, at a size a CPU test run holds."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, common, run
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
