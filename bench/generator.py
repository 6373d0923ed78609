"""The general traffic generator: how a window drives the program.

A traffic file (``bench/traffic/<mix>.json``) names one of the kinds
below and gives its parameters; nothing else about a mix lives in code.

- ``chunked``: a batch embedding.  The window starts a fresh
  ``init_state`` and dispatches the scan-chunked program
  (``make_chunked_step``, ``iters_per_dispatch`` iterations each, under
  the configuration's schedule) until ``--seconds`` have passed, reading
  each dispatch's metrics as ``fit`` does, ``ahead_s`` seconds of
  dispatches behind the newest.
- ``frames``: one interactive client in a closed loop.  Each frame
  uploads the current drag's hyperparameters, runs one ``make_step``,
  reads the embedding back and probes HD recall on a fixed sample
  against exact lists computed in set-up.  The drag walks the mix's
  phases in the file's order, ``frames_per_phase`` frames each, and
  cycles.  (A drag order drawn from the seed changed how soon recall
  rises by up to a third: every seed gets the same drags.)
- ``mesh_chunked``: ``chunked`` on a mesh of the cell's ``chips``, as
  ``runtime/coordinator.fit_elastic`` drives it: the mesh from
  ``elastic.remesh`` at the mix's ``model`` width, X sharded by
  features over ``model``, the state replicated, and the program
  ``make_distributed_step`` (see :func:`mesh_program`).

The seed draws the data.  A mix with ``program_seed`` gives the
program's own random stream (initial embedding, HD refinement gate,
candidates, negatives) that fixed key for every seed, so that every
seed's data meets the same gate draws and so nearly the same work.

Each kind has a ``setup`` (data, state, warm-up of exactly the
programs its window runs), a ``window``, and the steps of the output
check after it (``check_steps``, see ``bench/check.py``; one iteration
per dispatch).  Host spans of the window go to the profiler as
``bench.*`` annotations.
"""
from __future__ import annotations

import collections
import math
import statistics
import time

import numpy as np

from bench.check import STATE_POST, STATE_PRE, host_state, timed
from bench.data import generators
from bench.reference import knn as knn_ref
from bench.reference import lists as lists_ref
from bench.reference import step as step_ref


def base_hparams(n: int, overrides: dict) -> dict:
    """The program's default hyperparameters (openTSNE learning rate
    max(50, n/12)), as float32 numbers the reference shares."""
    hp = {"alpha": 1.0, "perplexity": 30.0, "lr": max(50.0, n / 12.0),
          "momentum": 0.8, "attraction": 1.0, "repulsion": 1.0,
          "exaggeration": 1.0}
    hp.update(overrides)
    return {k: np.float32(v) for k, v in hp.items()}


class Cell:
    """State shared by set-up, window and check of one run."""

    def __init__(self, jax, funcsne, spec: dict, seed: int, annotate):
        self.jax, self.funcsne = jax, funcsne
        self.config, self.traffic = spec["config"], spec["traffic"]
        self.annotate = annotate
        c = self.config
        self.n = c["n"]
        self.fs = dict(c["funcsne"])
        self.cfg = funcsne.FuncSNEConfig(n_points=c["n"], dim_hd=c["dim_hd"],
                                         dim_ld=c["dim_ld"], **self.fs)
        key = jax.random.PRNGKey(seed)
        self.data_key, self.init_key = jax.random.split(key)
        if "program_seed" in self.traffic:
            # the program's own random stream (initial embedding, HD gate,
            # candidates, negatives) is the mix's, the same for every
            # seed, so that each seed's data gets the same gate draws
            self.init_key = jax.random.PRNGKey(
                int(self.traffic["program_seed"]))
        self.X = generators.make(c, self.data_key)
        self.st = None
        self.result = {}

    def hp_device(self, hp: dict):
        return self.funcsne.HParams(**{k: hp[k] for k in
                                       self.funcsne.HParams._fields})

    def init(self):
        """``init_state`` as one compiled program: its eager loops would
        otherwise be lowered again on every call.  The input check it
        skips inside ``jit`` runs first, eagerly, as ``init_state`` runs
        it."""
        f = self.funcsne
        if not hasattr(self, "_init"):
            self._init = self.jax.jit(lambda key, X: f.init_state(
                key, X, self.cfg, validate=False))
        f.validate_inputs(self.X, self.cfg)
        return self._init(self.init_key, self.X)

    def gate_fires(self, st, emas) -> list:
        """Whether each step of the window refined the HD lists, redrawn
        from the state's key and the refinement share before each step
        (``emas[i]`` after step ``i``; the initial state's share is 1)."""
        key = host_state(self.jax, st, ())["key"]
        before = [1.0] + list(emas[:-1])
        return [lists_ref.gate_fires({"key": key, "step": i,
                                      "ema_new_frac": e}, self.fs)
                for i, e in enumerate(before)]

    def one(self, st, hp: dict):
        """One step of the window's program under ``hp``."""
        raise NotImplementedError

    def next_hp(self) -> dict:
        """The hyperparameters of the step the window would make next."""
        raise NotImplementedError

    def check_hps(self) -> list:
        """The hyperparameters of the check's steps: each set the
        window's traffic uses."""
        raise NotImplementedError

    def reference_hp(self, hp: dict, step: int) -> dict:
        """The hyperparameters a step at ``step`` under ``hp`` applies."""
        raise NotImplementedError

    def bytes_of(self, st, hp: dict):
        """Argument plus temporary bytes of the window's program as
        compiled for the device (``memory_analysis``); the compile is
        the window's own, found in the cache."""
        mem = self.prog.lower(st, self.X, self.hp_device(hp)).compile() \
            .memory_analysis()
        if mem is None:
            return None
        return int(mem.argument_size_in_bytes + mem.temp_size_in_bytes)

    def check_steps(self, seconds: dict | None = None):
        """(pre, [(post, hp)]): see ``bench/check.py``.  The window's
        program runs on to the next bandwidth refresh; every row is then
        marked as having new neighbours and the improvement share set to
        1, and one step is made from that state under each of
        ``check_hps``.  The seconds of the device steps to the refresh
        (``to_refresh``), of the check's steps (``steps``), of the copies
        to the host (``readback``) and of ``bytes_of`` are added to
        ``seconds``."""
        seconds = {} if seconds is None else seconds
        jax, jnp = self.jax, self.jax.numpy
        st, self.st = self.st, None
        with timed(seconds, "to_refresh"):
            while int(st.step) % self.cfg.sigma_refresh_every:
                st = self.one(st, self.next_hp())
            st = jax.block_until_ready(st._replace(
                new_flag=jnp.ones_like(st.new_flag),
                ema_new_frac=jnp.ones_like(st.ema_new_frac)))
        with timed(seconds, "readback"):
            pre = host_state(jax, st, STATE_PRE)
        hps = self.check_hps()
        with timed(seconds, "bytes_of"):
            self.program_bytes = self.bytes_of(st, hps[0])
        posts = []
        for i, hp in enumerate(hps):
            src = st if i == len(hps) - 1 else \
                jax.tree_util.tree_map(jnp.copy, st)
            with timed(seconds, "steps"):
                out = jax.block_until_ready(self.one(src, hp))
            with timed(seconds, "readback"):
                posts.append((host_state(jax, out, STATE_POST),
                              self.reference_hp(hp, int(pre["step"]))))
            del out
        return pre, posts


class Chunked(Cell):
    def setup(self):
        jax, f = self.jax, self.funcsne
        tr = self.traffic
        self.T = int(tr["iters_per_dispatch"])
        self.n_iter = int(tr["n_iter"])
        self.hp = base_hparams(self.n, tr.get("hparams", {}))
        self.prog = f.make_chunked_step(self.cfg, self.T,
                                        schedule=f.default_schedule,
                                        n_iter=self.n_iter)
        # warm: init_state's programs and one dispatch of the chunk
        st = self.init()
        st, _, m = self.prog(st, self.X, self.hp_device(self.hp))
        jax.block_until_ready((st, m))
        del st, m

    def window(self, seconds: float):
        """Dispatches run ``ahead_s`` seconds of steps ahead of the one
        whose metrics the host reads, so that a host stall does not leave
        the device idle.  When the time is up nothing more is sent; the
        window waits for all that was sent and reads the clock after
        that: every dispatched step counts, over all of that time."""
        jax = self.jax
        hp = self.hp_device(self.hp)
        ahead = float(self.traffic["ahead_s"])
        pending = collections.deque()
        dispatches = done = failed = 0
        emas = []

        def read_oldest():
            nonlocal done, failed
            with self.annotate("bench.sync"):
                finite, ema = jax.device_get(pending.popleft())
            emas.append(float(ema))
            done += 1
            failed += float(finite) < 1.0

        t0 = time.perf_counter()
        with self.annotate("bench.window"):
            with self.annotate("bench.init"):
                # waits, so that the init's device work ends in this span
                st = jax.block_until_ready(self.init())
            t_run = time.perf_counter()
            while time.perf_counter() - t0 < seconds:
                with self.annotate("bench.dispatch"):
                    st, _, m = self.prog(st, self.X, hp)
                pending.append((m.finite_frac, m.ema_new_frac))
                dispatches += 1
                # as many steps in flight as ``ahead_s`` holds at the
                # rate read so far (one until a step has been read)
                depth = 1 if not done else max(1, math.ceil(
                    ahead * done / (time.perf_counter() - t_run)))
                while len(pending) > depth:
                    read_oldest()
            while pending:
                read_oldest()
            jax.block_until_ready(st.Y)
        elapsed = time.perf_counter() - t0
        self.st = st
        iters = dispatches * self.T
        self.result = {"attempted": dispatches, "failed": int(failed),
                       "iterations": iters, "window_s": elapsed,
                       "metrics": {"iters_per_s": iters / elapsed},
                       "info": {"hd_fires": sum(self.gate_fires(st, emas))
                                if self.T == 1 else None}}

    def one(self, st, hp: dict):
        return self.prog(st, self.X, self.hp_device(hp))[0]

    def next_hp(self) -> dict:
        return self.hp

    def check_hps(self) -> list:
        # the chunk program applies the schedule to the base values
        return [self.hp]

    def reference_hp(self, hp: dict, step: int) -> dict:
        return step_ref.schedule(hp, step, self.n_iter)


class Frames(Cell):
    def setup(self):
        jax, f = self.jax, self.funcsne
        jnp = jax.numpy
        tr = self.traffic
        base = base_hparams(self.n, tr.get("hparams", {}))
        self.phase_hps = []
        for ph in tr["phases"]:
            hp = dict(base)
            for k, v in ph.items():
                if k == "lr_scale":
                    hp["lr"] = np.float32(base["lr"] * np.float32(v))
                elif k != "name":
                    hp[k] = np.float32(v)
            self.phase_hps.append(hp)
        self.per_phase = int(tr["frames_per_phase"])
        self.target = float(tr["recall_target"])
        rows = knn_ref.sample_rows(self.n, int(tr["recall_sample"]))
        self.rows = jnp.asarray(rows)
        self.true_idx, _ = knn_ref.exact_knn_rows(self.X, self.rows,
                                                  self.cfg.k_hd)
        self.prog = f.make_step(self.cfg)
        self.st = self.init()
        # warm the frame's programs on a copy (the step donates its state)
        warm = jax.tree_util.tree_map(jnp.copy, self.st)
        warm = self.prog(warm, self.X, self.hp_device(self.phase_hps[0]))
        jax.device_get((warm.Y, knn_ref.recall(warm.hd_idx, self.rows,
                                               self.true_idx)))
        del warm

    def frame_hp(self, i: int) -> dict:
        return self.phase_hps[(i // self.per_phase) % len(self.phase_hps)]

    def window(self, seconds: float):
        jax = self.jax
        st = self.st
        lat, recalls, emas, failed = [], [], [], 0
        ready = None
        t0 = time.perf_counter()
        with self.annotate("bench.window"):
            i = 0
            while True:
                t_f = time.perf_counter()
                with self.annotate("bench.frame"):
                    hp = self.frame_hp(i)
                    with self.annotate("bench.dispatch"):
                        st = self.prog(st, self.X, self.hp_device(hp))
                        probe = knn_ref.recall(st.hd_idx, self.rows,
                                               self.true_idx)
                    with self.annotate("bench.readback"):
                        Y, rec, ema = jax.device_get(
                            (st.Y, probe, st.ema_new_frac))
                    failed += not np.isfinite(Y).all()
                t_e = time.perf_counter()
                lat.append(t_e - t_f)
                recalls.append(float(rec))
                emas.append(float(ema))
                if ready is None and rec >= self.target:
                    ready = t_e - t0
                i += 1
                if t_e - t0 >= seconds:
                    break
        elapsed = time.perf_counter() - t0
        self.st, self.frames = st, i
        ms = [1e3 * x for x in lat]
        p90 = statistics.quantiles(ms, n=10, method="inclusive")[8]
        metrics = {"frame_ms_p90": p90}
        fires = self.gate_fires(st, emas)
        every = self.cfg.sigma_refresh_every
        kinds = {}
        for j, (t, fired) in enumerate(zip(ms, fires)):
            kinds.setdefault(("hd" if fired else "") + (
                "+sigma" if j % every == 0 else ""), []).append(t)
        if ready is not None:
            metrics["knn_ready_s"] = ready
        self.result = {"attempted": len(lat), "failed": int(failed),
                       "iterations": len(lat), "window_s": elapsed,
                       "metrics": metrics,
                       "numbers": {"recall_shortfall":
                                   self.target - max(recalls)},
                       "info": {"frame_ms_p50": statistics.median(ms),
                                "frames_above_p90": sum(t > p90 for t in ms),
                                "frame_ms_by_kind": {
                                    k or "plain": [len(v), statistics.median(v),
                                                   max(v)]
                                    for k, v in sorted(kinds.items())},
                                "recall_curve": [round(r, 4)
                                                 for r in recalls]}}

    def one(self, st, hp: dict):
        return self.prog(st, self.X, self.hp_device(hp))

    def next_hp(self) -> dict:
        self.frames += 1
        return self.frame_hp(self.frames - 1)

    def check_hps(self) -> list:
        return list(self.phase_hps)

    def reference_hp(self, hp: dict, step: int) -> dict:
        return hp


def mesh_program(jax, funcsne, cfg, traffic: dict, devices, chips: int):
    """(step program, init program, X sharding, state sharding) of a
    ``mesh_chunked`` mix on the first ``chips`` of ``devices``: the mesh,
    X's placement and the step as ``fit_elastic`` builds them.  The init
    is ``init_state`` as one program in which every device computes the
    whole state from the whole X (a Mosaic kernel is not partitioned but
    under ``shard_map``), so its outputs are replicated.  A mesh of fewer
    than ``chips`` devices is an error: a cell never runs on fewer chips
    than it asks for."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P
    from repro.runtime import elastic
    mesh = elastic.remesh(chips, model=int(traffic["model"]),
                          devices=devices[:chips], divides=(cfg.dim_hd,))
    if mesh.devices.size != chips:
        raise RuntimeError(f"the mesh {dict(mesh.shape)} uses "
                           f"{mesh.devices.size} of the {chips} chips the "
                           f"cell asks for")
    prog, _ = funcsne.make_distributed_step(
        cfg, mesh, chunk=int(traffic["iters_per_dispatch"]),
        schedule=funcsne.default_schedule, n_iter=int(traffic["n_iter"]))
    init = jax.jit(jax.shard_map(
        lambda key, X: funcsne.init_state(key, X, cfg, validate=False),
        mesh=mesh, in_specs=(P(), P()), out_specs=P(), check_vma=False))
    return prog, init, NamedSharding(mesh, P(None, "model")), \
        NamedSharding(mesh, P())


class MeshChunked(Chunked):
    """The ``chunked`` window over :func:`mesh_program` on the first
    ``chips`` devices; :meth:`Cell.init` runs the mesh's init program."""

    def __init__(self, jax, funcsne, spec: dict, seed: int, annotate):
        super().__init__(jax, funcsne, spec, seed, annotate)
        self.chips = int(spec["cell"]["chips"])

    def setup(self):
        jax = self.jax
        tr = self.traffic
        self.T = int(tr["iters_per_dispatch"])
        self.n_iter = int(tr["n_iter"])
        self.hp = base_hparams(self.n, tr.get("hparams", {}))
        self.prog, self._init, x_sharding, _ = mesh_program(
            jax, self.funcsne, self.cfg, tr, jax.devices(), self.chips)
        self.X = jax.device_put(self.X, x_sharding)
        # warm: the init program and one dispatch of the chunk
        st = self.init()
        st, _, m = self.prog(st, self.X, self.hp_device(self.hp))
        jax.block_until_ready((st, m))
        del st, m


KINDS = {"chunked": Chunked, "frames": Frames, "mesh_chunked": MeshChunked}


def make(jax, funcsne, spec: dict, seed: int, annotate) -> Cell:
    return KINDS[spec["traffic"]["kind"]](jax, funcsne, spec, seed, annotate)
