"""Ahead-of-time compiles of the main-path Pallas kernels and the default
chunk program for a TPU v5e that is described, not attached.

Interpret mode cannot see what Mosaic refuses (unaligned DMA slices,
SMEM layouts, scoped-VMEM overflows); the TPU compiler can, without a
chip.  Nothing here runs on a device.  All tests share one module-scoped
topology fixture, so only the worker given this file loads the TPU
compiler library.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import funcsne
from repro.kernels.knn_merge.kernel import (knn_merge_cand_pallas,
                                            knn_merge_pallas)
from repro.kernels.knn_merge.ops import merge_sources
from repro.kernels.ne_forces.kernel import (ne_forces_gather_pallas,
                                            ne_forces_scatter_pallas)
from repro.kernels.ne_forces.ops import row_source, scatter_chunk_plan
from repro.kernels.pairwise_sqdist.kernel import pairwise_sqdist_gather_pallas

N = 16384
CFG = funcsne.FuncSNEConfig(n_points=N, dim_hd=50, dim_ld=2,
                            backend="pallas")
SEGMENTS = (("attraction", CFG.k_hd), ("repulsion", CFG.k_ld),
            ("repulsion", CFG.n_negatives))
K_ALL = CFG.k_hd + CFG.k_ld + CFG.n_negatives


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip; keep it out of the cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def spec(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


def _tpu_kernels(compiled):
    """Instruction names of the Mosaic custom calls in a compiled module."""
    return re.findall(r"%([A-Za-z_]\w*?)(?:\.\d+)? = [^\n]*"
                      r'custom_call_target="tpu_custom_call"',
                      compiled.as_text())


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _i32(spec, *shape):
    return spec(shape, jnp.int32)


@pytest.mark.parametrize("m", [50, 128])
def test_sqdist_gather_compiles_for_v5e(spec, m):
    c = _compile(pairwise_sqdist_gather_pallas, spec((N, m)), _i32(spec, N),
                 _i32(spec, N, CFG.c_hd))
    assert _tpu_kernels(c)


@pytest.mark.parametrize("m", [50, 128])
def test_knn_merge_compiles_for_v5e(spec, m):
    c = _compile(lambda x, q, ci, cd, c, cv: knn_merge_pallas(
        x, q, ci, cd, c, cv, rescore=False),
        spec((N, m)), _i32(spec, N), _i32(spec, N, CFG.k_hd),
        spec((N, CFG.k_hd)), _i32(spec, N, CFG.c_hd),
        spec((N, CFG.c_hd), jnp.bool_))
    assert _tpu_kernels(c)


@pytest.mark.parametrize("m", [50, 128])
def test_knn_merge_cand_hd_compiles_for_v5e(spec, m):
    sources = (("two_hop", 0, 0, CFG.c_hd_non), ("one_hop", 1, CFG.c_hd_ld),
               ("two_hop", 1, 1, CFG.c_hd_ld_non),
               ("uniform", CFG.c_hd_rand))
    c = _compile(lambda x, q, ci, cd, salt, f0, f1, act: knn_merge_cand_pallas(
        x, q, ci, cd, salt, (f0, f1), (f0, f1), None, act, sources=sources,
        rescore=False),
        spec((N, m)), _i32(spec, N), _i32(spec, N, CFG.k_hd),
        spec((N, CFG.k_hd)), _i32(spec), _i32(spec, N, CFG.k_hd),
        _i32(spec, N, CFG.k_ld), spec((N,), jnp.bool_))
    assert _tpu_kernels(c) == ["knn_merge_cand"]


@pytest.mark.parametrize("d", [2, 32])
def test_knn_merge_cand_ld_compiles_for_v5e(spec, d):
    sources = (("two_hop", 0, 0, CFG.c_ld_non), ("one_hop", 1, CFG.c_ld_hd),
               ("uniform", CFG.c_ld_rand))
    c = _compile(lambda y, q, ci, cv, salt, f0, f1, act: knn_merge_cand_pallas(
        y, q, ci, cv, salt, (f0, f1), (f0,), None, act, sources=sources,
        rescore=True),
        spec((N, d)), _i32(spec, N), _i32(spec, N, CFG.k_ld),
        spec((N, CFG.k_ld), jnp.bool_), _i32(spec), _i32(spec, N, CFG.k_ld),
        _i32(spec, N, CFG.k_hd), spec((N,), jnp.bool_))
    assert _tpu_kernels(c) == ["knn_merge_cand"]


@pytest.mark.parametrize("n", [262144, 70000])
def test_knn_merge_cand_ld_vmem_sources_compile_for_v5e(spec, n):
    """The LD launch with its resident packed Y and flag tables at the
    benchmark cells' sizes (d=2), the packing included: one Mosaic
    launch, under its name."""
    assert merge_sources(n, 2) == ("vmem", "vmem")
    sources = (("two_hop", 0, 0, CFG.c_ld_non), ("one_hop", 1, CFG.c_ld_hd),
               ("uniform", CFG.c_ld_rand))
    c = _compile(lambda y, q, ci, cv, salt, f0, f1, act: knn_merge_cand_pallas(
        y, q, ci, cv, salt, (f0, f1), (f0,), None, act, sources=sources,
        rescore=True, row_source="vmem", flag_source="vmem"),
        spec((n, 2)), _i32(spec, n), _i32(spec, n, CFG.k_ld),
        spec((n, CFG.k_ld), jnp.bool_), _i32(spec), _i32(spec, n, CFG.k_ld),
        _i32(spec, n, CFG.k_hd), spec((n,), jnp.bool_))
    assert _tpu_kernels(c) == ["knn_merge_cand"]


@pytest.mark.parametrize("d", [2, 32])
def test_ne_forces_gather_compiles_for_v5e(spec, d):
    c = _compile(lambda y, q, nb, cf, a: ne_forces_gather_pallas(
        y, q, nb, cf, a, segments=SEGMENTS, emit_edges=(True, True, False)),
        spec((N, d)), _i32(spec, N), _i32(spec, N, K_ALL),
        spec((N, K_ALL)), spec(()))
    assert _tpu_kernels(c)


@pytest.mark.parametrize("n", [262144, 70000])
def test_ne_forces_gather_vmem_rows_compiles_for_v5e(spec, n):
    """The resident packed-table row source at the benchmark cells' sizes
    (d=2), the packing included: one Mosaic launch, under its name."""
    assert row_source(n, 2) == "vmem"
    c = _compile(lambda y, q, nb, cf, a: ne_forces_gather_pallas(
        y, q, nb, cf, a, segments=SEGMENTS, emit_edges=(True, True, False),
        row_source="vmem"),
        spec((n, 2)), _i32(spec, n), _i32(spec, n, K_ALL),
        spec((n, K_ALL)), spec(()))
    assert _tpu_kernels(c) == ["ne_forces_gather_pallas"]


@pytest.mark.parametrize("d", [2, 32])
def test_ne_forces_scatter_compiles_for_v5e(spec, d):
    chunk_n = scatter_chunk_plan(4 * N, d, len(SEGMENTS))
    c = _compile(lambda y, q, nb, cf, a: ne_forces_scatter_pallas(
        y, q, nb, cf, a, segments=SEGMENTS, scatter_back=(True, True, False),
        chunk_n=chunk_n),
        spec((4 * N, d)), _i32(spec, 4 * N), _i32(spec, 4 * N, K_ALL),
        spec((4 * N, K_ALL)), spec(()))
    assert _tpu_kernels(c)


@pytest.fixture(scope="module")
def chunk_compiled(spec):
    """The default ``make_chunked_step`` program (all fused flags
    at their defaults) compiled for one v5e chip at n=16384, M=50,
    d_ld=2."""
    assert CFG == funcsne.FuncSNEConfig(n_points=N, dim_hd=50,
                                        backend="pallas")
    x = jax.ShapeDtypeStruct((N, CFG.dim_hd), jnp.float32)
    st = jax.eval_shape(lambda x: funcsne.init_state(
        jax.random.PRNGKey(0), x, CFG, validate=False), x)
    hp = jax.eval_shape(lambda: funcsne.default_hparams(N))
    placed = jax.tree.map(lambda s: spec(s.shape, s.dtype), (st, x, hp))
    chunk = funcsne.make_chunked_step(CFG, 10,
                                      schedule=funcsne.default_schedule,
                                      n_iter=500)
    return chunk.lower(*placed).compile()


def test_default_chunk_program_compiles_for_v5e(chunk_compiled):
    """The whole default chunk program compiles, with Mosaic kernels for
    candidate-fused HD and LD refinement and for the forces."""
    kernels = _tpu_kernels(chunk_compiled)
    assert kernels.count("knn_merge_cand") == 2, kernels
    assert "ne_forces_gather_pallas" in kernels, kernels


def _op_names(compiled, kernel):
    """``op_name`` metadata of each launch of ``kernel`` in a module."""
    return [re.search(r'op_name="([^"]*)"', line).group(1)
            for line in compiled.as_text().splitlines()
            if re.match(rf"\s*(?:ROOT )?%{kernel}(?:\.\d+)? = ", line)]


def test_chunk_program_force_kernel_reads_vmem_rows(chunk_compiled):
    """At d=2 the step's one force launch takes the resident row source:
    exactly one ``ne_forces_gather*`` kernel, named
    ``ne_forces_gather_pallas``, traced under ``ne_forces.vmem_rows``."""
    kernels = _tpu_kernels(chunk_compiled)
    forces = [k for k in kernels if k.startswith("ne_forces_gather")]
    assert forces == ["ne_forces_gather_pallas"], kernels
    assert sorted(kernels) == ["knn_merge_cand", "knn_merge_cand",
                               "ne_forces_gather_pallas"], kernels
    scopes = _op_names(chunk_compiled, "ne_forces_gather_pallas")
    assert len(scopes) == 1, scopes
    assert "/ne_forces.vmem_rows/" in scopes[0], scopes


def test_chunk_program_merge_sources(chunk_compiled):
    """At d=2 the LD merge reads resident packed Y rows and the HD merge
    (M=50) row DMAs; both read resident activity flags.  Each launch is
    still named ``knn_merge_cand`` and keeps its phase scope outside the
    source scopes."""
    scopes = {}
    for name in _op_names(chunk_compiled, "knn_merge_cand"):
        phase = PHASE.findall(name)
        assert len(phase) == 1, name
        scopes[phase[0]] = name
    assert sorted(scopes) == ["hd_refine", "ld_refine"], scopes
    for phase, rows in (("ld_refine", "vmem"), ("hd_refine", "dma")):
        name = scopes[phase]
        assert f"/knn_merge.{rows}_rows/knn_merge.vmem_flags/" in name, name
        assert name.index(f"funcsne.{phase}") \
            < name.index("knn_merge."), name


PHASE = re.compile(r"funcsne\.(hd_refine|sigma_refresh|ld_refine|"
                   r"forces_update)(?=/|$)")


def test_chunk_program_ops_carry_their_phase_scope(chunk_compiled):
    """Every Mosaic kernel of the compiled chunk program keeps exactly one
    ``funcsne.<phase>`` scope in its ``op_name`` metadata: the HD merge
    under ``hd_refine``, the LD merge under ``ld_refine``, the force
    kernel under ``forces_update``; every phase owns some instruction."""
    kernels, seen = [], set()
    for line in chunk_compiled.as_text().splitlines():
        m = re.search(r'op_name="([^"]*)"', line)
        found = PHASE.findall(m.group(1)) if m else []
        seen.update(found)
        if 'custom_call_target="tpu_custom_call"' in line:
            name = re.match(r"\s*(?:ROOT )?%([A-Za-z_]\w*?)(?:\.\d+)? = ",
                            line).group(1)
            assert len(found) == 1, line[:300]
            kernels.append((name, found[0]))
    assert sorted(kernels) == [("knn_merge_cand", "hd_refine"),
                               ("knn_merge_cand", "ld_refine"),
                               ("ne_forces_gather_pallas", "forces_update")]
    assert seen == {"hd_refine", "sigma_refresh", "ld_refine",
                    "forces_update"}
