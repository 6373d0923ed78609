"""Fault-tolerant checkpointing: async, atomic, elastic, multi-host,
*verified*.

Design (single-host container standing in for a multi-host pod):
  - save(): device_get the pytree off the step path (async thread by
    default), write one .npz per checkpoint with path-flattened keys, commit
    atomically via tmp-dir rename.  On a pod each host writes ONLY its own
    shard file (``host_shard_filter`` + ``host_id``/``n_hosts``): parts are
    staged under the shared tmp dir and the host that completes the set
    commits, so checkpoint I/O scales with hosts instead of funnelling
    through one.  Elastic control planes additionally tag shards with a
    ``generation`` (``shard<h>-of-<H>-g<G>.npz``): the completing writer
    evicts stale-generation leftovers from the staging dir and the
    reader loads only the committing generation's manifest entries, so a
    half-dead generation's shards can never merge with a relaunch's.
  - integrity manifest: every save records, in ``meta.json``, a per-shard
    CRC32 of the file bytes plus an array manifest (key, dtype, shape,
    row range) -- computed from the in-memory bytes it is about to write,
    so the manifest is the ground truth a later reader can check the disk
    against.
  - verify_step(): re-reads every shard file and checks (a) the CRC32,
    (b) the exact array set with dtype/shape, (c) row coverage -- every
    host-sliced leaf covered exactly once, no gaps/overlaps across the
    ``shard*-of-*.npz`` set -- and (d) internal n_hosts consistency.
    Any violation raises a structured :class:`CheckpointCorrupt` naming
    the step, file and reason; a torn write, bit flip or deleted shard is
    detected *before* anything is materialised into device memory.
  - restore(): verify (on by default), then load, merging per-host shard
    files by row offset; ``device_put`` with the *target* mesh's
    NamedShardings -- a checkpoint written on a 512-chip mesh restores
    onto 256 chips (elastic re-sharding) because arrays are stored
    unsharded (or as host-row slices that merge to unsharded) and
    re-laid-out on load.  ``expect_compat=`` additionally checks the
    writer's config fingerprint (:func:`cfg_compat`: n, dims, K, flag
    matrix) against the restorer's and raises
    :class:`CheckpointIncompatible` on mismatch -- a cfg-mismatched
    resume fails structurally instead of silently loading garbage.
  - restore_verified(): the fallback chain -- walk committed steps
    newest -> oldest until one verifies, returning which damaged
    boundaries were skipped so the caller can log a
    ``checkpoint_fallback`` event per skip.  The step that verified is
    remembered and ``keep_last`` pruning never evicts it: graceful
    degradation must not saw off the branch it is standing on.
  - keep_last: old committed checkpoints are pruned (0 keeps nothing,
    except the last *verified* boundary, see above).
  - metadata (step, data cursor, RNG, hyperparams) rides along as JSON.
  - error surfacing: an async write failure raises on the next ``wait()``
    or ``save()``; ``close()`` (and ``__del__``) *warn* on an error nobody
    ever observed, so the final checkpoint of a run cannot vanish silently.

``python -m repro.checkpoint.verify <dir>`` runs the same verification
as an offline fsck over every committed step of a checkpoint directory.

QTensor (int8 optimiser moments) leaves flatten into q/scale arrays like
any other pytree node.
"""
from __future__ import annotations

import io
import json
import os
import shutil
import threading
import time
import warnings
import zlib
from pathlib import Path
from typing import Any, Callable, List, Optional

import jax
import numpy as np

_SEP = "||"
_ROWS = "@rows"     # key suffix marking a host-sliced leaf: key||@rows<start>
_MANIFEST_SUFFIX = ".manifest.json"     # staged per-shard sidecar (tmp only)


# --------------------------------------------------------------------------
# Structured errors


class CheckpointError(RuntimeError):
    """Base class for structured checkpoint failures."""


class CheckpointNotFound(CheckpointError, FileNotFoundError):
    """The requested step (or any step at all) is not committed.

    Attributes:
      step:      the step requested (None = latest).
      available: the committed steps actually present, oldest first.
    """

    def __init__(self, directory, step: Optional[int],
                 available: List[int]):
        what = "no checkpoints" if step is None \
            else f"no checkpoint for step {step}"
        super().__init__(
            f"{what} under {directory}; available steps: "
            f"{available if available else '(none)'}")
        self.step = step
        self.available = list(available)


class CheckpointCorrupt(CheckpointError):
    """A committed checkpoint failed integrity verification.

    Attributes:
      step:   the step that failed.
      path:   the step directory.
      reason: what exactly failed (missing shard, CRC mismatch, row
              coverage gap/overlap, dtype/shape drift, ...).
    """

    def __init__(self, path, step: int, reason: str):
        super().__init__(
            f"checkpoint step {step} under {path} failed verification: "
            f"{reason}")
        self.step = step
        self.path = str(path)
        self.reason = reason


class CheckpointIncompatible(CheckpointError):
    """The checkpoint verifies but was written under an incompatible
    config (different n / dims / K / fused-flag matrix): restoring it
    would poison the resumed run rather than continue it.

    Attributes:
      step:       the step checked.
      mismatches: ``{field: (checkpoint_value, expected_value)}``.
    """

    def __init__(self, path, step: int, mismatches: dict):
        diffs = ", ".join(f"{k}: checkpoint={a!r} != expected={b!r}"
                          for k, (a, b) in sorted(mismatches.items()))
        super().__init__(
            f"checkpoint step {step} under {path} is incompatible with "
            f"the resuming config: {diffs}")
        self.step = step
        self.path = str(path)
        self.mismatches = mismatches


def cfg_compat(cfg) -> dict:
    """Restore-compatibility fingerprint of a ``FuncSNEConfig``-like
    object: the fields a resumed run must agree on for the restored
    state to mean the same thing (array geometry) and for the random
    streams to continue bit-identically (the step's flags; the three
    locked ``*_fused`` fields stay so older checkpoints still restore).
    Duck-typed so the checkpoint layer never imports ``repro.core``.
    """
    return {
        "n": int(cfg.n_points), "dim_hd": int(cfg.dim_hd),
        "dim_ld": int(cfg.dim_ld), "k_hd": int(cfg.k_hd),
        "k_ld": int(cfg.k_ld), "c_hd_rev": int(cfg.c_hd_rev),
        "flags": {
            "gather_fused": bool(cfg.gather_fused),
            "scatter_fused": bool(cfg.scatter_fused),
            "merge_fused": bool(cfg.merge_fused),
            "cand_fused": bool(cfg.cand_fused),
        },
    }


def _compat_mismatches(recorded: dict, expected: dict, prefix="") -> dict:
    out = {}
    for k, want in expected.items():
        have = recorded.get(k) if isinstance(recorded, dict) else None
        if isinstance(want, dict):
            out.update(_compat_mismatches(have or {}, want,
                                          prefix=f"{prefix}{k}."))
        elif have != want:
            out[f"{prefix}{k}"] = (have, want)
    return out


def _flatten(tree) -> dict:
    flat = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        key = _SEP.join(str(p) for p in path)
        flat[key] = np.asarray(leaf)
    return flat


def _unflatten_into(tree, flat: dict):
    leaves, treedef = jax.tree_util.tree_flatten_with_path(tree)
    out = []
    for path, leaf in leaves:
        key = _SEP.join(str(p) for p in path)
        if key not in flat:
            raise KeyError(f"checkpoint missing leaf {key}")
        out.append(flat[key])
    return jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(tree), out)


def row_shard_filter(host_id: int, n_hosts: int, n_rows: int) -> Callable:
    """Standard per-host filter: host ``h`` persists rows
    ``[h*n/H, (h+1)*n/H)`` of every leaf whose leading dim is ``n_rows``;
    host 0 additionally persists every other (replicated / scalar) leaf.
    Feed the result to :meth:`Checkpointer.save` as ``host_shard_filter``.
    """
    def filt(key: str, arr: np.ndarray):
        if arr.ndim >= 1 and arr.shape[0] == n_rows:
            lo = host_id * n_rows // n_hosts
            hi = (host_id + 1) * n_rows // n_hosts
            return lo, arr[lo:hi]
        return (None, arr) if host_id == 0 else None
    return filt


class Checkpointer:
    def __init__(self, directory, keep_last: int = 3):
        self.dir = Path(directory)
        self.dir.mkdir(parents=True, exist_ok=True)
        self.keep_last = keep_last
        self._thread: Optional[threading.Thread] = None
        self.last_error: Optional[BaseException] = None
        # last step that PASSED verification: pruning never evicts it,
        # so the fallback chain always has a floor to land on
        self._verified_step: Optional[int] = None

    # -- save ------------------------------------------------------------

    def save(self, step: int, tree: Any, metadata: dict = None,
             blocking: bool = False, host_shard_filter: Callable = None,
             host_id: int = 0, n_hosts: int = 1,
             generation: Optional[int] = None):
        """Snapshot is taken synchronously (device_get); I/O is async.

        ``host_shard_filter(key, array)`` selects what THIS host writes:
        ``None`` skips the leaf (another host owns it), ``(None, arr)``
        writes it whole, ``(start, rows)`` writes a row slice merged back
        by offset on restore (see :func:`row_shard_filter`).  With
        ``n_hosts > 1`` each host stages ``shard<h>-of-<H>.npz`` under
        the shared tmp dir and the host completing the set commits; a
        step directory is therefore only ever visible fully merged.

        ``generation`` (elastic runtimes: the pod-incarnation number the
        control plane bumps on every relaunch) tags the shard files --
        ``shard<h>-of-<H>-g<G>.npz`` -- and is recorded in the metadata.
        The completing writer only counts ITS generation's parts toward
        the set and EVICTS every stale-generation file still staged in
        the tmp dir before committing, so shards written by a generation
        that died mid-checkpoint can never be merged into a later
        generation's boundary (the reader additionally loads only the
        files named by the committing generation's manifest).  With a
        generation the staged-shard layout is used even for
        ``n_hosts == 1``, keeping tag semantics uniform across remesh
        widths.
        """
        self.wait()
        host_tree = jax.tree.map(np.asarray, jax.device_get(tree))
        meta = dict(metadata or {})
        meta["step"] = int(step)
        meta["time"] = time.time()
        meta["n_hosts"] = int(n_hosts)
        if generation is not None:
            meta["generation"] = int(generation)

        flat, arrays_meta = {}, {}
        for key, arr in _flatten(host_tree).items():
            if host_shard_filter is None:
                picked = (None, arr)
            else:
                picked = host_shard_filter(key, arr)
                if picked is None:
                    continue
            start, part = picked
            entry = {"dtype": str(part.dtype), "shape": list(part.shape)}
            if start is None:
                flat[key] = part
            else:
                entry["rows"] = [int(start), int(start) + int(part.shape[0])]
                entry["full_rows"] = int(arr.shape[0])
                key = f"{key}{_SEP}{_ROWS}{int(start)}"
                flat[key] = part
            arrays_meta[key] = entry

        def write():
            try:
                # serialise in memory first: the CRC32 in the manifest is
                # computed over the exact bytes that hit the disk
                buf = io.BytesIO()
                np.savez(buf, **flat)
                blob = buf.getvalue()
                file_meta = {"crc32": zlib.crc32(blob) & 0xFFFFFFFF,
                             "arrays": arrays_meta}
                tmp = self.dir / f".tmp-{step}"
                final = self.dir / f"step_{step:010d}"
                gen_tag = "" if generation is None \
                    else f"-g{int(generation):06d}"
                if n_hosts == 1 and generation is None:
                    # single writer: no commit race is possible, so the
                    # overwrite-an-existing-step semantics are safe here
                    if tmp.exists():
                        shutil.rmtree(tmp)
                    tmp.mkdir(parents=True)
                    (tmp / "arrays.npz").write_bytes(blob)
                    meta["manifest"] = {"n_hosts": 1,
                                        "files": {"arrays.npz": file_meta}}
                    (tmp / "meta.json").write_text(json.dumps(meta))
                    if final.exists():
                        shutil.rmtree(final)
                    os.rename(tmp, final)          # atomic commit
                else:
                    # multi-writer staging: parts land independently,
                    # the completing host commits.  Each host stages its
                    # manifest sidecar BEFORE the npz becomes visible, so
                    # a visible shard always has its manifest on disk.
                    tmp.mkdir(parents=True, exist_ok=True)
                    part = tmp / (f"shard{host_id:03d}-of-{n_hosts:03d}"
                                  f"{gen_tag}.npz")
                    (tmp / (part.name + _MANIFEST_SUFFIX)).write_text(
                        json.dumps(file_meta))
                    part_tmp = part.with_suffix(".npz.tmp")
                    part_tmp.write_bytes(blob)
                    os.replace(part_tmp, part)
                    parts = sorted(
                        tmp.glob(f"shard*-of-{n_hosts:03d}{gen_tag}.npz"))
                    if len(parts) < n_hosts:
                        return          # another host completes the set
                    # Exactly ONE completing writer may commit: real
                    # SPMD processes hit the boundary near-
                    # simultaneously, so BOTH can glob a full set.  The
                    # commit is claimed with an O_EXCL marker beside the
                    # staging dir; the race's loser backs off here
                    # instead of renaming (or deleting!) the winner's
                    # just-committed step dir.  The claim is generation-
                    # tagged so a claim left by a writer that died mid-
                    # commit can never block a relaunched generation
                    # from committing the same step.
                    claim = self.dir / f".tmp-{step}.claim{gen_tag}"
                    try:
                        os.close(os.open(str(claim),
                                         os.O_CREAT | os.O_EXCL
                                         | os.O_WRONLY))
                    except FileExistsError:
                        return  # the other completing writer commits
                    try:
                        files = {}
                        for p in parts:
                            side = tmp / (p.name + _MANIFEST_SUFFIX)
                            files[p.name] = json.loads(side.read_text())
                            side.unlink()
                        if generation is not None:
                            # completing writer owns the commit: any
                            # file still staged that is NOT part of this
                            # generation's set is a stale shard (or torn
                            # tmp/sidecar) from a generation that died
                            # mid-checkpoint -- evict it so it can
                            # neither merge into this boundary nor
                            # linger on disk
                            keep = {p.name for p in parts}
                            evicted = []
                            for f in sorted(tmp.iterdir()):
                                if f.name not in keep:
                                    f.unlink()
                                    evicted.append(f.name)
                            if evicted:
                                meta["evicted_stale"] = evicted
                        meta["manifest"] = {"n_hosts": n_hosts,
                                            "files": files}
                        (tmp / "meta.json").write_text(json.dumps(meta))
                        # never pre-delete `final` here: with the claim
                        # released post-commit a straggling writer can
                        # still reach this point, and an rmtree would
                        # destroy the committed boundary elastic resume
                        # depends on.  rename IS the atomic commit; its
                        # failure with the boundary present just means
                        # the other writer won.
                        os.rename(tmp, final)
                    except OSError:
                        if (final / "meta.json").exists():
                            # lost the race: the boundary is committed
                            claim.unlink(missing_ok=True)
                            return
                        raise
                    for c in self.dir.glob(f".tmp-{step}.claim*"):
                        try:
                            c.unlink()
                        except OSError:     # pragma: no cover
                            pass
                self._prune()
            except BaseException as e:        # surfaced on next wait()
                self.last_error = e

        if blocking:
            write()
            if self.last_error is not None:   # blocking callers want it NOW
                err, self.last_error = self.last_error, None
                raise err
        else:
            self._thread = threading.Thread(target=write, daemon=True)
            self._thread.start()

    def wait(self):
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        if self.last_error is not None:
            err, self.last_error = self.last_error, None
            raise err

    def close(self):
        """Join any in-flight write; WARN (never raise) on an error that
        no ``wait()`` ever observed.  Safe on error-handling paths where
        raising would mask the in-flight exception."""
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        if self.last_error is not None:
            err, self.last_error = self.last_error, None
            warnings.warn(
                f"[checkpoint] async write under {self.dir} failed and the "
                f"error was never observed by wait(): {err!r} -- the last "
                f"checkpoint of this run may be missing", RuntimeWarning,
                stacklevel=2)

    def __del__(self):
        # a Checkpointer dropped with a pending failure must not take the
        # evidence with it; never join/raise during interpreter teardown
        err = getattr(self, "last_error", None)
        if err is not None:
            self.last_error = None      # deliver once
            try:
                warnings.warn(
                    f"[checkpoint] Checkpointer({self.dir}) garbage-"
                    f"collected with an unobserved write error: {err!r}",
                    RuntimeWarning, stacklevel=2)
            except Exception:       # pragma: no cover - teardown races
                pass

    def _prune(self):
        steps = self.all_steps()
        # keep_last=0 keeps NOTHING: guard the [:-0] empty slice that
        # would silently keep everything
        drop = steps if self.keep_last <= 0 else steps[:-self.keep_last]
        for s in drop:
            if s == self._verified_step:
                # never evict the boundary the fallback chain last landed
                # on: newer checkpoints exist but have NOT been verified,
                # so this is the only committed step known to be good
                continue
            shutil.rmtree(self.dir / f"step_{s:010d}", ignore_errors=True)

    # -- restore ---------------------------------------------------------

    def all_steps(self):
        return sorted(int(p.name.split("_")[1]) for p in self.dir.glob(
            "step_*") if (p / "meta.json").exists())

    def latest_step(self) -> Optional[int]:
        steps = self.all_steps()
        return steps[-1] if steps else None

    def _load_merged(self, d: Path, meta: Optional[dict] = None) -> dict:
        """Load one committed step dir, merging per-host shard files:
        plain keys load as-is, ``key||@rows<start>`` slices concat by
        offset.  The single-host ``arrays.npz`` layout is the n_hosts=1
        special case of the same reader.

        When ``meta`` carries a manifest, ONLY the files it names are
        read: the manifest was written by the generation that committed
        the boundary, so a stale-generation shard that somehow survived
        into the directory is filtered out rather than merged (the
        verifying reader additionally flags it as a stray)."""
        man = (meta or {}).get("manifest")
        if isinstance(man, dict) and man.get("files"):
            files = [d / name for name in sorted(man["files"])]
        else:
            files = sorted(d.glob("shard*-of-*.npz"))
            if not files:
                files = [d / "arrays.npz"]
        flat, sliced = {}, {}
        for f in files:
            with np.load(f, allow_pickle=False) as z:
                for key in z.files:
                    if _SEP + _ROWS in key:
                        base, _, start = key.rpartition(_SEP + _ROWS)
                        sliced.setdefault(base, []).append(
                            (int(start), z[key]))
                    else:
                        flat[key] = z[key]
        for base, parts in sliced.items():
            parts.sort(key=lambda p: p[0])
            flat[base] = np.concatenate([a for _, a in parts], axis=0) \
                if len(parts) > 1 else parts[0][1]
        return flat

    # -- verify ----------------------------------------------------------

    def verify_step(self, step: int) -> dict:
        """Full integrity check of one committed step WITHOUT
        materialising anything: CRC32 of every shard file, exact array
        set with dtype/shape, row coverage (each host-sliced leaf covered
        exactly once, no gaps/overlaps) and internal n_hosts consistency.
        Returns the checkpoint metadata on success; raises
        :class:`CheckpointCorrupt` naming the failure otherwise."""
        d = self.dir / f"step_{step:010d}"
        if not (d / "meta.json").exists():
            raise CheckpointNotFound(self.dir, step, self.all_steps())
        try:
            meta = json.loads((d / "meta.json").read_text())
        except (OSError, ValueError) as e:
            raise CheckpointCorrupt(d, step, f"meta.json unreadable: {e}")
        man = meta.get("manifest")
        if not isinstance(man, dict) or "files" not in man:
            raise CheckpointCorrupt(
                d, step, "meta.json carries no integrity manifest "
                "(checkpoint predates verification?)")
        want_files = man["files"]
        have = sorted(p.name for p in d.glob("*.npz"))
        missing = sorted(set(want_files) - set(have))
        stray = sorted(set(have) - set(want_files))
        if missing:
            raise CheckpointCorrupt(
                d, step, f"missing shard file(s): {missing}")
        if stray:
            raise CheckpointCorrupt(
                d, step, f"file(s) not in manifest: {stray}")
        if int(man.get("n_hosts", len(want_files))) != len(want_files):
            raise CheckpointCorrupt(
                d, step, f"manifest n_hosts={man.get('n_hosts')} but "
                f"{len(want_files)} shard file(s) recorded")

        coverage = {}   # base key -> [(start, stop, full_rows, fname)]
        plain_seen = {}  # base key -> fname (unsliced leaves)
        for fname, fman in sorted(want_files.items()):
            try:
                blob = (d / fname).read_bytes()
            except OSError as e:
                raise CheckpointCorrupt(d, step, f"{fname}: unreadable: {e}")
            crc = zlib.crc32(blob) & 0xFFFFFFFF
            if crc != int(fman["crc32"]):
                raise CheckpointCorrupt(
                    d, step, f"{fname}: CRC32 mismatch "
                    f"(file {crc:#010x} != manifest "
                    f"{int(fman['crc32']) & 0xFFFFFFFF:#010x})")
            try:
                with np.load(io.BytesIO(blob), allow_pickle=False) as z:
                    info = {k: (str(z[k].dtype), list(z[k].shape))
                            for k in z.files}
            except Exception as e:
                raise CheckpointCorrupt(
                    d, step, f"{fname}: unloadable npz despite matching "
                    f"CRC: {e}")
            want_arrays = fman.get("arrays", {})
            if set(want_arrays) != set(info):
                gone = sorted(set(want_arrays) - set(info))
                extra = sorted(set(info) - set(want_arrays))
                raise CheckpointCorrupt(
                    d, step, f"{fname}: array set drifted from manifest "
                    f"(missing {gone}, unexpected {extra})")
            for key, am in want_arrays.items():
                dt, shp = info[key]
                if dt != am["dtype"] or shp != list(am["shape"]):
                    raise CheckpointCorrupt(
                        d, step, f"{fname}: {key}: {dt}{shp} != manifest "
                        f"{am['dtype']}{list(am['shape'])}")
                if "rows" in am:
                    lo, hi = int(am["rows"][0]), int(am["rows"][1])
                    if hi - lo != shp[0]:
                        raise CheckpointCorrupt(
                            d, step, f"{fname}: {key}: row range "
                            f"[{lo}, {hi}) disagrees with leading dim "
                            f"{shp[0]}")
                    base = key.rpartition(_SEP + _ROWS)[0]
                    coverage.setdefault(base, []).append(
                        (lo, hi, int(am["full_rows"]), fname))
                else:
                    if key in plain_seen:
                        raise CheckpointCorrupt(
                            d, step, f"leaf {key} written whole by both "
                            f"{plain_seen[key]} and {fname}")
                    plain_seen[key] = fname
        for base, parts in coverage.items():
            if base in plain_seen:
                raise CheckpointCorrupt(
                    d, step, f"leaf {base} written both whole "
                    f"({plain_seen[base]}) and row-sliced")
            full = {p[2] for p in parts}
            if len(full) != 1:
                raise CheckpointCorrupt(
                    d, step, f"leaf {base}: shards disagree on full row "
                    f"count: {sorted(full)}")
            n_rows = full.pop()
            pos = 0
            for lo, hi, _, fname in sorted(parts):
                if lo > pos:
                    raise CheckpointCorrupt(
                        d, step, f"leaf {base}: rows [{pos}, {lo}) "
                        f"uncovered")
                if lo < pos:
                    raise CheckpointCorrupt(
                        d, step, f"leaf {base}: rows [{lo}, {pos}) "
                        f"covered twice ({fname})")
                pos = hi
            if pos != n_rows:
                raise CheckpointCorrupt(
                    d, step, f"leaf {base}: rows [{pos}, {n_rows}) "
                    f"uncovered")
        return meta

    def _check_compat(self, d, step, meta, expect_compat):
        if expect_compat is None:
            return
        mism = _compat_mismatches(meta.get("compat") or {}, expect_compat)
        if mism:
            raise CheckpointIncompatible(d, step, mism)

    def restore(self, like_tree: Any, step: Optional[int] = None,
                shardings: Any = None, verify: bool = True,
                expect_compat: Optional[dict] = None):
        """Returns (tree, metadata).  ``shardings``: optional NamedSharding
        tree for the *target* mesh (elastic re-shard on load -- the mesh
        may be smaller than the one that wrote the checkpoint).

        ``verify=True`` (default) runs :meth:`verify_step` first, raising
        :class:`CheckpointCorrupt` before anything touches device memory.
        ``expect_compat`` (a :func:`cfg_compat` dict) raises
        :class:`CheckpointIncompatible` when the checkpoint was written
        under a different config fingerprint.  A missing step (or an
        empty directory) raises :class:`CheckpointNotFound` naming the
        available steps."""
        steps = self.all_steps()
        if step is None:
            if not steps:
                raise CheckpointNotFound(self.dir, None, [])
            step = steps[-1]
        elif step not in steps:
            raise CheckpointNotFound(self.dir, step, steps)
        d = self.dir / f"step_{step:010d}"
        if verify:
            meta = self.verify_step(step)
            self._verified_step = step
        else:
            meta = json.loads((d / "meta.json").read_text())
        self._check_compat(d, step, meta, expect_compat)
        flat = self._load_merged(d, meta)
        tree = _unflatten_into(like_tree, flat)
        tree = jax.tree.map(
            lambda ref, x: np.asarray(x).astype(ref.dtype).reshape(ref.shape),
            like_tree, tree)
        if shardings is not None:
            tree = jax.tree.map(
                lambda x, s: jax.device_put(x, s), tree, shardings)
        return tree, meta

    def restore_verified(self, like_tree: Any, step: Optional[int] = None,
                         shardings: Any = None,
                         expect_compat: Optional[dict] = None):
        """Fallback-chain restore: walk committed steps newest -> oldest
        (at most ``step``, when given) until one passes verification.

        Returns ``(tree, metadata, fallbacks)`` where ``fallbacks`` lists
        ``{"step", "reason"}`` for every damaged boundary that was
        skipped -- callers log one ``checkpoint_fallback`` event per
        entry.  Raises :class:`CheckpointNotFound` when nothing is
        committed, :class:`CheckpointCorrupt` when every committed step
        is damaged, and :class:`CheckpointIncompatible` immediately on a
        config mismatch (every boundary of a run shares its config, so
        falling back would only mask the user error)."""
        steps = self.all_steps()
        if step is not None:
            steps = [s for s in steps if s <= step]
        if not steps:
            raise CheckpointNotFound(self.dir, step, self.all_steps())
        fallbacks = []
        for s in reversed(steps):
            try:
                tree, meta = self.restore(like_tree, step=s,
                                          shardings=shardings,
                                          expect_compat=expect_compat)
            except CheckpointCorrupt as e:
                fallbacks.append({"step": s, "reason": e.reason})
                continue
            return tree, meta, fallbacks
        raise CheckpointCorrupt(
            self.dir, steps[-1],
            "every committed step failed verification: " + "; ".join(
                f"step {f['step']}: {f['reason']}" for f in fallbacks))
