"""Multi-device integration tests (subprocess: 8 fake CPU devices).

XLA locks the device count at first jax init, so these run in fresh
subprocesses with XLA_FLAGS set; the parent pytest process keeps 1 device.
"""
import os
import subprocess
import sys
import textwrap

import pytest

# distributed-parity suite: every test pays a subprocess + 8-device
# compile; excluded from the tier-1 PR gate, run on the schedule
pytestmark = pytest.mark.slow

_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(code: str, timeout=560):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = _SRC
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=timeout,
                       env=env)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


def test_funcsne_distributed_step_improves_knn():
    out = _run("""
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro import compat
        from repro.data.synthetic import blobs
        from repro.core import funcsne
        from repro.core.quality import knn_set_quality

        X, _ = blobs(n=512, dim=16, n_centers=5, center_std=6.0)
        Xj = jnp.asarray(X)
        mesh = compat.make_mesh((4, 2), ("data", "model"))
        cfg = funcsne.FuncSNEConfig(n_points=512, dim_hd=16)
        st = funcsne.init_state(jax.random.PRNGKey(0), Xj, cfg)
        q0 = float(knn_set_quality(st.hd_idx, Xj))
        step, _ = funcsne.make_distributed_step(cfg, mesh)
        Xs = jax.device_put(Xj, NamedSharding(mesh, P(None, "model")))
        st = jax.device_put(st, NamedSharding(mesh, P()))
        hp = funcsne.default_hparams(512)
        for _ in range(150):
            st = step(st, Xs, hp)
        q1 = float(knn_set_quality(st.hd_idx, Xj))
        assert q1 > max(q0 + 0.2, 0.8), (q0, q1)
        assert bool(jnp.isfinite(st.Y).all())
        print("OK", q0, "->", q1)
    """)
    assert "OK" in out


def test_funcsne_distributed_scatter_fused_matches_legacy_epilogue():
    """The force psum consuming scatter-fused kernel partials must produce
    the same displacement field as the legacy edge-scatter epilogue on a
    (data, model) mesh.  Both paths quantise the psum to bf16 (Perf
    H10a), so a few steps with a loose tolerance is the honest bound --
    per-step fp32 parity is pinned single-device in test_scatter_fused.py.
    """
    out = _run("""
        import dataclasses, jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro import compat
        from repro.data.synthetic import blobs
        from repro.core import funcsne

        X, _ = blobs(n=512, dim=16, n_centers=5, center_std=6.0)
        Xj = jnp.asarray(X)
        mesh = compat.make_mesh((4, 2), ("data", "model"))
        cfg_s = funcsne.FuncSNEConfig(n_points=512, dim_hd=16,
                                      backend="xla", scatter_fused=True)
        cfg_l = dataclasses.replace(cfg_s, scatter_fused=False)
        st0 = funcsne.init_state(jax.random.PRNGKey(0), Xj, cfg_s)
        hp = funcsne.default_hparams(512)
        Xs = jax.device_put(Xj, NamedSharding(mesh, P(None, "model")))

        def run(cfg):
            step, _ = funcsne.make_distributed_step(cfg, mesh)
            # the step donates its state: hand each run its own copy
            st = jax.device_put(jax.tree.map(lambda a: jnp.array(a,
                                                                 copy=True),
                                             st0),
                                NamedSharding(mesh, P()))
            for _ in range(8):
                st = step(st, Xs, hp)
            return st

        st_s, st_l = run(cfg_s), run(cfg_l)
        assert bool(jnp.isfinite(st_s.Y).all())
        np.testing.assert_allclose(np.asarray(st_s.Y), np.asarray(st_l.Y),
                                   rtol=5e-2, atol=5e-3)
        np.testing.assert_allclose(float(st_s.zhat), float(st_l.zhat),
                                   rtol=2e-2)
        print("OK scatter-fused == legacy on mesh")
    """)
    assert "OK" in out


def test_funcsne_distributed_chunked_step_matches_sequential():
    """make_distributed_step(chunk=T) on a (data, model) mesh == T
    sequential distributed dispatches: discrete state bit-equal, float
    state to fp32 tolerance (the while-body codegen context costs ulps,
    same as single-device -- see tests/test_chunked_driver.py), and the
    snapshot ring + metrics come back replicated."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro import compat
        from repro.data.synthetic import blobs
        from repro.core import funcsne

        X, _ = blobs(n=256, dim=16, n_centers=5, center_std=6.0)
        Xj = jnp.asarray(X)
        mesh = compat.make_mesh((4, 2), ("data", "model"))
        cfg = funcsne.FuncSNEConfig(n_points=256, dim_hd=16, backend="xla")
        st0 = funcsne.init_state(jax.random.PRNGKey(0), Xj, cfg)
        hp = funcsne.default_hparams(256)
        Xs = jax.device_put(Xj, NamedSharding(mesh, P(None, "model")))
        cp = lambda s: jax.device_put(
            jax.tree.map(lambda a: jnp.array(a, copy=True), s),
            NamedSharding(mesh, P()))

        T = 6
        step, _ = funcsne.make_distributed_step(cfg, mesh)
        st_seq = cp(st0)
        for _ in range(T):
            st_seq = step(st_seq, Xs, hp)

        chunk, _ = funcsne.make_distributed_step(cfg, mesh, chunk=T,
                                                 snapshot_every=3)
        st_c, snaps, metrics = chunk(cp(st0), Xs, hp)
        assert int(metrics.step) == T and int(metrics.n_snapshots) == 2
        assert snaps.shape[1:] == (256, 2), snaps.shape
        # every shard draws the gates from replicated state: the mesh
        # counts the single-device program's fires
        _, _, m1 = funcsne.make_chunked_step(cfg, T)(
            jax.tree.map(lambda a: jnp.array(a, copy=True), st0), Xj, hp)
        assert (int(metrics.hd_fires), int(metrics.sigma_fires)) == \
            (int(m1.hd_fires), int(m1.sigma_fires)) == (T, 1)
        for name in funcsne.FuncSNEState._fields:
            a = np.asarray(getattr(st_c, name))
            b = np.asarray(getattr(st_seq, name))
            if a.dtype.kind != 'f':
                np.testing.assert_array_equal(a, b, err_msg=name)
            else:
                finite = np.isfinite(b)
                scale = float(np.max(np.abs(b[finite]))) + 1e-9
                np.testing.assert_allclose(a[finite], b[finite], rtol=1e-4,
                                           atol=1e-5 * scale, err_msg=name)
        print("OK distributed chunk == sequential")
    """)
    assert "OK" in out


def test_lm_train_step_compiles_and_runs_on_mesh():
    out = _run("""
        import dataclasses, jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro import compat
        from repro.configs.base import get_arch, smoke_variant
        from repro.launch.mesh import sanitize_spec, tree_shardings
        from repro.launch.steps import (batch_struct, make_model,
                                        make_optimizer, make_train_step)
        mesh = compat.make_mesh((4, 2), ("data", "model"))
        cfg = dataclasses.replace(smoke_variant(get_arch("olmoe-1b-7b")),
                                  attn_chunk_k=64)
        model = make_model(cfg, mesh)
        opt = make_optimizer(cfg)
        params = model.init_params(jax.random.PRNGKey(0))
        opt_state = opt.init(params)
        p_sh = tree_shardings(mesh, model.param_specs(),
                              jax.eval_shape(lambda: params))
        params = jax.device_put(params, p_sh)
        step = jax.jit(make_train_step(model, opt), donate_argnums=(0, 1))
        x = jax.random.randint(jax.random.PRNGKey(1), (8, 64), 0,
                               cfg.vocab_size)
        batch = {"inputs": x, "labels": x}
        p2, o2, metrics = step(params, opt_state, batch)
        loss0 = float(metrics["loss"])
        for i in range(3):
            p2, o2, metrics = step(p2, o2, batch)
        assert float(metrics["loss"]) < loss0
        print("OK", loss0, "->", float(metrics["loss"]))
    """)
    assert "OK" in out


def test_checkpoint_elastic_reshard():
    out = _run("""
        import tempfile, jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro import compat
        from repro.checkpoint import Checkpointer

        mesh8 = compat.make_mesh((4, 2), ("data", "model"))
        mesh4 = compat.make_mesh((2, 2), ("data", "model"),
                                 devices=jax.devices()[:4])
        t = {"w": jax.device_put(jnp.arange(64, dtype=jnp.float32)
                                 .reshape(8, 8),
                                 NamedSharding(mesh8, P("data", "model")))}
        d = tempfile.mkdtemp()
        ck = Checkpointer(d)
        ck.save(1, t, blocking=True)
        got, _ = ck.restore(jax.tree.map(jnp.zeros_like, t),
                            shardings={"w": NamedSharding(
                                mesh4, P("data", "model"))})
        np.testing.assert_array_equal(np.asarray(got["w"]),
                                      np.asarray(t["w"]))
        assert got["w"].sharding.mesh.devices.size == 4
        print("OK elastic reshard 8 -> 4 devices")
    """)
    assert "OK" in out


def test_multipod_gradient_compression_psum():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro import compat
        from repro.optim.compression import (compress_with_error_feedback,
                                             init_ef)
        mesh = compat.make_mesh((2, 4), ("pod", "data"))

        def allreduce_compressed(g, ef):
            sparse, ef, dens = compress_with_error_feedback(
                {"g": g}, ef, k_frac=0.25)
            summed = jax.lax.psum(sparse["g"], "pod")
            return summed, ef

        f = compat.shard_map(
            lambda g, r: (jax.lax.psum(g, "pod"), r),
            mesh=mesh, in_specs=(jax.sharding.PartitionSpec("pod"),
                                 jax.sharding.PartitionSpec()),
            out_specs=(jax.sharding.PartitionSpec(),
                       jax.sharding.PartitionSpec()), check_vma=False)
        g = jnp.arange(16, dtype=jnp.float32).reshape(2, 8)
        s, _ = f(g, jnp.zeros((8,)))
        np.testing.assert_allclose(np.asarray(s).reshape(-1),
                                   np.asarray(g.sum(0)))
        print("OK pod-axis psum")
    """)
    assert "OK" in out
