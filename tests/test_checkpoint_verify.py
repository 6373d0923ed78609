"""Verified checkpoints: CRC/manifest integrity, fallback-chain restore,
compat fingerprints, prune protection, the state auditor, and the
offline fsck CLI.

The trust contracts pinned here are the ones ISSUE 9 promises:
  * any damage to a committed checkpoint (truncated, bit-flipped or
    deleted shard file; row-coverage gaps) is detected at restore time
    as a structured CheckpointCorrupt -- never materialised;
  * restore_verified walks newest -> oldest to the last intact boundary
    and reports every boundary it skipped;
  * pruning never evicts the last VERIFIED boundary;
  * a cfg-mismatched resume raises CheckpointIncompatible instead of
    silently loading garbage; a matching-cfg resume stays bit-identical;
  * audit_state counts exactly the invariant violations it claims to,
    and zero on healthy states.
"""
import io
import json
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import (CheckpointCorrupt, CheckpointError,
                              CheckpointIncompatible, CheckpointNotFound,
                              Checkpointer, cfg_compat, row_shard_filter)
from repro.checkpoint.verify import verify_dir
from repro.core import funcsne
from repro.core.funcsne import FuncSNEConfig
from repro.core.knn import SENTINEL
from repro.core.resilience import ResiliencePolicy
from repro.runtime.faults import (CorruptShard, FaultScript, Preempted,
                                  Preemption, active)

N, DIM = 48, 5


def _data(n=N, dim=DIM, seed=0):
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(2, dim)) * 5.0
    X = centers[rng.integers(0, 2, size=n)] + rng.normal(size=(n, dim))
    return jnp.asarray(X, jnp.float32)


def _cfg(n=N, dim=DIM, **kw):
    kw.setdefault("backend", "xla")
    kw.setdefault("n_negatives", 4)
    kw.setdefault("k_hd", min(32, n // 2))
    kw.setdefault("k_ld", min(16, n // 4))
    return FuncSNEConfig(n_points=n, dim_hd=dim, **kw)


def _tree(n=12, d=2, seed=0):
    rng = np.random.default_rng(seed)
    return {"Y": jnp.asarray(rng.normal(size=(n, d)), jnp.float32),
            "idx": jnp.asarray(rng.integers(0, n, size=(n, 3)), jnp.int32),
            "step": jnp.int32(7)}


def _like(n=12, d=2):
    return {"Y": np.zeros((n, d), np.float32),
            "idx": np.zeros((n, 3), np.int32), "step": np.int32(0)}


def _save_steps(ck, steps, n=12, n_hosts=1, meta=None):
    tree = _tree(n=n)
    for s in steps:
        if n_hosts == 1:
            ck.save(s, tree, metadata=dict(meta or {}), blocking=True)
        else:
            for h in range(n_hosts):
                ck.save(s, tree, metadata=dict(meta or {}),
                        host_shard_filter=row_shard_filter(h, n_hosts, n),
                        host_id=h, n_hosts=n_hosts)
            ck.wait()
    return tree


def _shard_files(ck, step):
    d = ck.dir / f"step_{step:010d}"
    return sorted(d.glob("shard*-of-*.npz")) or [d / "arrays.npz"]


# ---------------------------------------------------------------------------
# Manifest + verify


def test_save_writes_manifest_and_roundtrip_verifies(tmp_path):
    ck = Checkpointer(tmp_path, keep_last=5)
    tree = _save_steps(ck, [3])
    meta = json.loads(
        (tmp_path / "step_0000000003" / "meta.json").read_text())
    man = meta["manifest"]
    assert man["n_hosts"] == 1 and set(man["files"]) == {"arrays.npz"}
    fman = man["files"]["arrays.npz"]
    assert isinstance(fman["crc32"], int)
    y_meta = next(v for k, v in fman["arrays"].items() if "'Y'" in k)
    assert y_meta["dtype"] == "float32"
    assert y_meta["shape"] == [12, 2]
    got, m = ck.restore(_like())
    assert m["step"] == 3
    np.testing.assert_array_equal(np.asarray(got["Y"]),
                                  np.asarray(tree["Y"]))


def test_multihost_manifest_records_row_ranges(tmp_path):
    ck = Checkpointer(tmp_path, keep_last=5)
    tree = _save_steps(ck, [1], n_hosts=3)
    meta = ck.verify_step(1)
    man = meta["manifest"]
    assert man["n_hosts"] == 3 and len(man["files"]) == 3
    spans = []
    for fman in man["files"].values():
        for key, am in fman["arrays"].items():
            if "rows" in am and "'Y'" in key:
                assert am["full_rows"] == 12
                spans.append(tuple(am["rows"]))
    assert sorted(spans) == [(0, 4), (4, 8), (8, 12)]
    # no sidecar manifests survive the commit
    assert not list((tmp_path / "step_0000000001").glob("*.manifest.json"))
    got, _ = ck.restore(_like())
    np.testing.assert_array_equal(np.asarray(got["Y"]),
                                  np.asarray(tree["Y"]))


@pytest.mark.parametrize("mode", ["truncate", "bitflip", "delete"])
@pytest.mark.parametrize("n_hosts", [1, 2])
def test_damage_detected_at_restore(tmp_path, mode, n_hosts):
    ck = Checkpointer(tmp_path, keep_last=5)
    _save_steps(ck, [4], n_hosts=n_hosts)
    target = _shard_files(ck, 4)[-1]
    if mode == "delete":
        target.unlink()
    elif mode == "truncate":
        target.write_bytes(target.read_bytes()[:40])
    else:
        blob = bytearray(target.read_bytes())
        blob[len(blob) // 2] ^= 0x04
        target.write_bytes(bytes(blob))
    with pytest.raises(CheckpointCorrupt) as ei:
        ck.restore(_like())
    assert ei.value.step == 4
    assert isinstance(ei.value, CheckpointError)


def test_row_coverage_gap_detected(tmp_path):
    # surgically rewrite the manifest so the file set is self-consistent
    # but rows [6, 12) of every sliced leaf are missing: only the
    # coverage check can catch this
    ck = Checkpointer(tmp_path, keep_last=5)
    _save_steps(ck, [2], n_hosts=2)
    d = tmp_path / "step_0000000002"
    meta = json.loads((d / "meta.json").read_text())
    gone = "shard001-of-002.npz"
    del meta["manifest"]["files"][gone]
    meta["manifest"]["n_hosts"] = 1
    (d / gone).unlink()
    (d / "meta.json").write_text(json.dumps(meta))
    with pytest.raises(CheckpointCorrupt) as ei:
        ck.verify_step(2)
    assert "uncovered" in ei.value.reason


def test_stray_file_and_missing_manifest_detected(tmp_path):
    ck = Checkpointer(tmp_path, keep_last=5)
    _save_steps(ck, [1])
    d = tmp_path / "step_0000000001"
    (d / "extra.npz").write_bytes(b"junk")
    with pytest.raises(CheckpointCorrupt, match="not in manifest"):
        ck.verify_step(1)
    (d / "extra.npz").unlink()
    meta = json.loads((d / "meta.json").read_text())
    del meta["manifest"]
    (d / "meta.json").write_text(json.dumps(meta))
    with pytest.raises(CheckpointCorrupt, match="manifest"):
        ck.verify_step(1)


# ---------------------------------------------------------------------------
# Structured not-found + fallback chain


def test_restore_missing_step_names_available(tmp_path):
    ck = Checkpointer(tmp_path, keep_last=5)
    with pytest.raises(CheckpointNotFound) as ei:
        ck.restore(_like())
    assert ei.value.available == []
    assert isinstance(ei.value, FileNotFoundError)   # back-compat catch
    _save_steps(ck, [2, 5])
    with pytest.raises(CheckpointNotFound) as ei:
        ck.restore(_like(), step=3)
    assert ei.value.available == [2, 5] and ei.value.step == 3
    with pytest.raises(CheckpointNotFound):
        ck.restore_verified(_like(), step=1)   # nothing committed <= 1


def test_restore_verified_walks_to_last_intact_boundary(tmp_path):
    ck = Checkpointer(tmp_path, keep_last=5)
    tree = _save_steps(ck, [1, 2, 3])
    for s in (2, 3):    # damage the two newest
        f = _shard_files(ck, s)[0]
        f.write_bytes(f.read_bytes()[:30])
    got, meta, fbs = ck.restore_verified(_like())
    assert meta["step"] == 1
    assert [f["step"] for f in fbs] == [3, 2]
    assert all("CRC32" in f["reason"] or "truncat" in f["reason"].lower()
               or f["reason"] for f in fbs)
    np.testing.assert_array_equal(np.asarray(got["Y"]),
                                  np.asarray(tree["Y"]))
    # every boundary damaged -> structured aggregate, not a fall-through
    f = _shard_files(ck, 1)[0]
    f.unlink()
    with pytest.raises(CheckpointCorrupt, match="every committed step"):
        ck.restore_verified(_like())


def test_prune_never_evicts_last_verified_boundary(tmp_path):
    ck = Checkpointer(tmp_path, keep_last=1)
    _save_steps(ck, [1, 2, 3])
    assert ck.all_steps() == [3]        # keep_last=1 pruned 1 and 2
    got, meta, fbs = ck.restore_verified(_like())
    assert meta["step"] == 3 and fbs == []
    # newer saves arrive; they have NOT been verified, so pruning must
    # keep the boundary the restore chain last landed on
    _save_steps(ck, [4, 5])
    assert 3 in ck.all_steps(), \
        "pruning evicted the last verified boundary"
    assert 5 in ck.all_steps()
    # verifying a newer step moves the protection forward: 3 is now
    # prunable again
    ck.restore_verified(_like())        # lands on 5
    _save_steps(ck, [6])
    assert ck.all_steps() == [5, 6]


# ---------------------------------------------------------------------------
# Compat fingerprints


def test_cfg_compat_mismatch_raises_structured(tmp_path):
    cfg = _cfg()
    ck = Checkpointer(tmp_path, keep_last=5)
    _save_steps(ck, [2], meta={"compat": cfg_compat(cfg)})
    # matching cfg restores fine
    ck.restore(_like(), expect_compat=cfg_compat(cfg))
    for other in (_cfg(n=N + 16),                      # n differs
                  _cfg(dim=DIM + 1),                   # d differs
                  _cfg(scatter_fused=not cfg.scatter_fused)):  # flags
        with pytest.raises(CheckpointIncompatible) as ei:
            ck.restore(_like(), expect_compat=cfg_compat(other))
        assert ei.value.mismatches, ei.value
    # incompat must NOT fall back to older boundaries (same-run cfg is
    # constant; falling back would mask a user error)
    _save_steps(ck, [3], meta={"compat": cfg_compat(cfg)})
    with pytest.raises(CheckpointIncompatible):
        ck.restore_verified(_like(),
                            expect_compat=cfg_compat(_cfg(n=N + 16)))


def test_fit_resume_mismatched_cfg_raises(tmp_path):
    X, cfg = _data(), _cfg()
    policy = ResiliencePolicy(checkpoint_dir=str(tmp_path),
                              checkpoint_every=1)
    funcsne.fit(X, cfg=cfg, n_iter=8, chunk_size=4, resilience=policy)
    bad_cfg = _cfg(scatter_fused=not cfg.scatter_fused)
    with pytest.raises(CheckpointIncompatible):
        funcsne.fit(X, cfg=bad_cfg, n_iter=8, chunk_size=4,
                    resilience=ResiliencePolicy(),
                    resume_from=str(tmp_path))


def test_fit_corrupt_fallback_resume_bit_identical(tmp_path):
    """The PR-6 resume guarantee survives a damaged newest boundary:
    resume falls back one chunk and replays it bit-identically."""
    X, cfg = _data(), _cfg()
    kw = dict(cfg=cfg, n_iter=16, chunk_size=4)
    st_ref, _ = funcsne.fit(X, resilience=ResiliencePolicy(), **kw)

    fault = CorruptShard(at_step=8, mode="truncate")
    with pytest.raises(Preempted):
        with active(FaultScript(fault, Preemption(at_step=8))):
            funcsne.fit(X, resilience=ResiliencePolicy(
                checkpoint_dir=str(tmp_path), checkpoint_every=1), **kw)
    assert fault.damaged is not None
    policy = ResiliencePolicy(checkpoint_dir=str(tmp_path),
                              checkpoint_every=1)
    st_res, _ = funcsne.fit(X, resilience=policy,
                            resume_from=str(tmp_path), **kw)
    fbs = [e for e in policy.events if e["kind"] == "checkpoint_fallback"]
    assert fbs and fbs[0]["step"] == 8, policy.events
    np.testing.assert_array_equal(np.asarray(st_res.Y),
                                  np.asarray(st_ref.Y))
    assert int(st_res.step) == 16


# ---------------------------------------------------------------------------
# State auditor units


def _state(cfg=None, n=N):
    cfg = cfg or _cfg(n=n)
    X = _data(n=n)
    return X, cfg, funcsne.init_state(jax.random.PRNGKey(0), X, cfg)


def test_audit_clean_state_all_zero():
    X, cfg, st = _state()
    res = jax.device_get(funcsne.audit_state(st, cfg, X))
    assert all(int(v) == 0 for v in res), res._asdict()
    policy = ResiliencePolicy()
    assert policy.audit_check(res) is None


def test_audit_counts_oob_dup_sentinel_nonfinite():
    X, cfg, st = _state()
    policy = ResiliencePolicy()

    bad = st._replace(hd_idx=st.hd_idx.at[0, 0].set(N + 5))
    res = jax.device_get(funcsne.audit_state(bad, cfg))
    assert int(res.hd_oob) == 1 and int(res.ld_oob) == 0
    assert "hd_oob=1" in policy.audit_check(res)

    # rev_idx is (N, 0) when reverse edges are off: vacuously clean
    res = jax.device_get(funcsne.audit_state(st, cfg))
    assert int(res.rev_oob) == 0
    Xr, cfg_r, st_r = _state(cfg=_cfg(c_hd_rev=4))
    bad = st_r._replace(rev_idx=st_r.rev_idx.at[0, 0].set(-3))
    res = jax.device_get(funcsne.audit_state(bad, cfg_r))
    assert int(res.rev_oob) == 1

    dup = st._replace(
        hd_idx=st.hd_idx.at[0, 0].set(int(st.hd_idx[0, 1])))
    res = jax.device_get(funcsne.audit_state(dup, cfg))
    assert int(res.hd_dup) >= 1

    # SENTINEL idx slot with a finite distance: phantom neighbour
    sent = st._replace(hd_idx=st.hd_idx.at[0, 0].set(SENTINEL),
                       hd_d=st.hd_d.at[0, 0].set(1.0))
    res = jax.device_get(funcsne.audit_state(sent, cfg))
    assert int(res.hd_sentinel) == 1
    # SENTINEL with +inf distance is the healthy encoding
    ok = st._replace(hd_idx=st.hd_idx.at[0, 0].set(SENTINEL),
                     hd_d=st.hd_d.at[0, 0].set(jnp.inf))
    res = jax.device_get(funcsne.audit_state(ok, cfg))
    assert int(res.hd_sentinel) == 0 and int(res.hd_oob) == 0

    nan = st._replace(Y=st.Y.at[0, 0].set(jnp.nan))
    res = jax.device_get(funcsne.audit_state(nan, cfg))
    assert int(res.y_nonfinite) == 1
    # the same NaN on an INACTIVE row is not a violation
    nan_off = nan._replace(active=nan.active.at[0].set(False))
    res = jax.device_get(funcsne.audit_state(nan_off, cfg))
    assert int(res.y_nonfinite) == 0

    Xbad = X.at[1, 0].set(jnp.nan)
    res = jax.device_get(funcsne.audit_state(st, cfg, Xbad))
    assert int(res.x_nonfinite) == 1
    res = jax.device_get(funcsne.audit_state(st, cfg))   # no X given
    assert int(res.x_nonfinite) == 0


# ---------------------------------------------------------------------------
# Offline fsck CLI


def test_verify_cli_reports_damage_and_exit_code(tmp_path):
    from repro.checkpoint import verify as vmod

    ck = Checkpointer(tmp_path, keep_last=5)
    _save_steps(ck, [1, 2])
    f = _shard_files(ck, 2)[0]
    blob = bytearray(f.read_bytes())
    blob[len(blob) // 2] ^= 0x01
    f.write_bytes(bytes(blob))

    out = io.StringIO()
    assert verify_dir(tmp_path, out=out) == 1
    text = out.getvalue()
    assert "step 1: OK" in text and "step 2: CORRUPT" in text
    assert "CRC32" in text
    assert vmod.main([str(tmp_path)]) == 1
    assert vmod.main([str(tmp_path), "--step", "1"]) == 0
    assert vmod.main([str(tmp_path), "--step", "9"]) == 1
    shutil.rmtree(tmp_path / "step_0000000002")
    assert vmod.main([str(tmp_path)]) == 0
