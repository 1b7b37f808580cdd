"""Compile the main path for a TPU v5e chip that is described, not attached.

Interpret mode runs a kernel body on the CPU and cannot see what the chip's
compiler refuses (block shapes off the (8, 128) tiling, copies of
non-contiguous rows, scratch over budget).  These tests lower each Pallas
kernel with ``interpret=False`` through its ``ops`` wrapper at the paper's
audio widths (dim 192, degree 20) and at dim 128 / degree 30, and check
that the compiled program holds the kernel.  They also compile the default
jnp ``range_search`` program at an audio-size corpus and check that it
fits one chip's memory, and Alg. 4's single-lane search at the Enron table
in the index's table format and in the compiler's default one.

The topology is described inside a module fixture, never at import: only
one process may load the TPU compiler's library, and only the worker that
runs this file should.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

#: one v5e chip's HBM (Google Cloud, "TPU v5e")
V5E_HBM_BYTES = 16 * 2**30

#: (corpus rows, dim, degree): the paper's audio deployment, and a
#: dim-128 / degree-30 deployment (sift1m-like widths)
SHAPES = [(53387, 192, 20), (65536, 128, 30)]
BATCH = 64
#: the paper's Enron deployment: 1369-wide rows, 11 lane tiles a row
ENRON = (94987, 1369, 30)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_program(name, sharding, N, m, d):
    """(jitted fn, argument specs) for one kernel's ops wrapper."""
    from repro.kernels.beam_merge import ops as bm
    from repro.kernels.fused_hop import ops as fh
    from repro.kernels.gather_dist import ops as gd
    from repro.kernels.gather_dist_q import ops as gdq
    from repro.kernels.mrng_occlusion import ops as mo
    from repro.kernels.pq_adc import ops as pq
    from repro.quant.pq import n_subspaces, subspace_dim

    S = lambda shape, dt=jnp.float32: _spec(sharding, shape, dt)  # noqa: E731
    B = BATCH
    if name == "fused_hop":
        E = 4
        return (lambda a, v, s, q, r, vis, nv: fh.fused_hop(
                    a, v, s, q, r, vis, n_valid=nv, backend="pallas",
                    interpret=False),
                [S((N, d), jnp.int32), S((N, m)), S((B, E), jnp.int32),
                 S((B, m)), S((B,)), S((B, 2048), jnp.int32),
                 S((), jnp.int32)])
    if name == "gather_dist":
        return (lambda v, i, q: gd.gather_dist(v, i, q, interpret=False),
                [S((N, m)), S((B, d), jnp.int32), S((B, m))])
    if name == "gather_dist_q":
        return (lambda c, s, i, q: gdq.gather_dist_q(c, s, i, q,
                                                     interpret=False),
                [S((N, m), jnp.int8), S((m,)), S((B, d), jnp.int32),
                 S((B, m))])
    if name == "pq_adc":
        m_sub, dsub = n_subspaces(m), subspace_dim(m)
        return (lambda c, cb, i, q: pq.pq_adc(c, cb, i, q, interpret=False),
                [S((N, m_sub), jnp.uint8), S((m_sub, 256, dsub)),
                 S((B, d), jnp.int32), S((B, m))])
    if name == "mrng_occlusion":
        W, K = 16, 2 * d          # one extension block, k_ext candidates
        return (lambda v, i, q, c, w: mo.mrng_occlusion(
                    v, i, q, c, w, backend="pallas", interpret=False),
                [S((N, m)), S((W, K, d), jnp.int32), S((W, m)),
                 S((W, K)), S((W, K, d))])
    if name == "beam_merge":
        L, C = 80, 4 * d          # default k_ext beam, an E=4 hop's block
        return (lambda *a: bm.beam_merge(*a, backend="pallas",
                                         interpret=False),
                [S((B, L)), S((B, L), jnp.int32), S((B, L), jnp.bool_),
                 S((B, L), jnp.bool_), S((B, C)), S((B, C), jnp.int32),
                 S((B, C), jnp.bool_)])
    raise KeyError(name)


@pytest.mark.parametrize("N,m,d", SHAPES, ids=lambda v: str(v))
@pytest.mark.parametrize("name", ["fused_hop", "gather_dist",
                                  "gather_dist_q", "pq_adc",
                                  "mrng_occlusion", "beam_merge"])
def test_kernel_compiles_for_v5e(one_chip, name, N, m, d):
    fn, specs = _kernel_program(name, one_chip, N, m, d)
    compiled = jax.jit(fn).lower(*specs).compile()
    assert "tpu_custom_call" in compiled.as_text(), name


def test_default_range_search_fits_v5e(one_chip):
    """The served default: jnp hop, E=1, one batch bucket of 64 queries
    over the audio-size corpus."""
    from repro.core.graph import DEGraph
    from repro.core.search import range_search

    N, m, d = SHAPES[0]
    S = lambda shape, dt=jnp.float32: _spec(one_chip, shape, dt)  # noqa: E731
    graph = DEGraph(adjacency=S((N, d), jnp.int32), weights=S((N, d)),
                    n=S((), jnp.int32))
    compiled = range_search.lower(
        graph, S((N, m)), S((BATCH, m)), S((BATCH, 1), jnp.int32),
        k=10, eps=0.1).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 0 < used < V5E_HBM_BYTES, mem
    assert "tpu_custom_call" not in compiled.as_text()


#: the filtered deployment (yfcc-filter-192-l2): rows, dim, degree, tag
#: slots, and the scan's chunks a lane (L x degree rows of 256)
FILTERED = (100000, 192, 20, 16, 10)


@pytest.mark.parametrize("program", ["beam", "scan"])
def test_filtered_programs_fit_v5e(one_chip, program):
    """A filtered flush's two programs at the filtered deployment's size:
    the beam with the tag table and a (64, 2) filter operand, and the
    scan of its widest chunk bucket."""
    from repro.core.graph import DEGraph
    from repro.core.search import filtered_scan, range_search

    N, m, d, T, J = FILTERED
    S = lambda shape, dt=jnp.float32: _spec(one_chip, shape, dt)  # noqa: E731
    if program == "beam":
        graph = DEGraph(adjacency=S((N, d), jnp.int32), weights=S((N, d)),
                        n=S((), jnp.int32))
        lowered = range_search.lower(
            graph, S((N, m)), S((BATCH, m)), S((BATCH, 1), jnp.int32),
            k=10, eps=0.2, beam_width=128, tags=S((N, T), jnp.int32),
            filter_tags=S((BATCH, 2), jnp.int32))
    else:
        C = BATCH * J
        lowered = filtered_scan.lower(S((N, m)), S((BATCH, m)),
                                      S((C, 256), jnp.int32),
                                      S((C,), jnp.int32),
                                      S((BATCH, J), jnp.int32), k=10)
    mem = lowered.compile().memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 0 < used < V5E_HBM_BYTES, mem


@pytest.mark.parametrize("table", ["search_format", "default_format"])
def test_refine_search_reads_table_in_place(one_chip, table):
    """Alg. 4's single-lane search (1 lane, 2 seeds, k 30, eps 0.001) over
    the Enron table.  In the index's table format the program copies no
    table-sized array.  In the compiler's default format, column-major for
    these rows (it pads less), it relayouts the whole table before the
    first hop: the reason the format exists."""
    from repro.analysis.hlo import result_copies
    from repro.core.graph import DEGraph
    from repro.core.search import range_search, table_format

    N, m, d = ENRON
    S = lambda shape, dt=jnp.float32: _spec(one_chip, shape, dt)  # noqa: E731
    fmt = (table_format(one_chip, (N, m)) if table == "search_format"
           else one_chip)
    graph = DEGraph(adjacency=S((N, d), jnp.int32), weights=S((N, d)),
                    n=S((), jnp.int32))
    text = range_search.lower(
        graph, _spec(fmt, (N, m), jnp.float32), S((1, m)),
        S((1, 2), jnp.int32), k=30, eps=0.001).compile().as_text()
    copies = result_copies(text, "f32", (N, m))
    if table == "search_format":
        assert copies == []
    else:
        assert copies


@pytest.mark.parametrize("N,m,staged", [(53387, 192, True),
                                        (94987, 1369, False)])
def test_table_format_follows_on_chip_memory(one_chip, N, m, staged):
    """A table whose row-major bytes fit a v5e core's on-chip memory keeps
    the default layout, which the compiler stages on chip row-major (audio:
    55 MB of 128 MiB); a larger one is kept row-major (Enron: 535 MB)."""
    from repro.core.search import TABLE_LAYOUT, table_format

    layout = table_format(one_chip, (N, m)).layout
    assert layout == (None if staged else TABLE_LAYOUT)
