"""Ahead-of-time compiles of the LOOPS panel kernels for a described TPU v5e.

The TPU compiler is installed alongside jax, and it compiles for a chip that
is described rather than attached, so these tests catch what interpret mode
cannot: blocks that break the (8, 128) tiling rule, scalar-prefetched
metadata beyond SMEM, and contractions Mosaic cannot lower.  Nothing runs;
a compile that passes is not a chip run.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and the test workers
all import this file.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.bcsr_spmm import bcsr_panels_spmm_pallas
from repro.kernels.csr_spmm import csr_panels_spmm_pallas
from repro.kernels.panel_common import panels_per_call
from repro.kernels.spmm_sdd import (bcsr_sdd_panels_pallas,
                                    csr_sdd_panels_pallas)

P = 4096       # panels: one SMEM chunk at G=4
G = 4
K = 8192       # rows of the dense operand
M = 4096       # output rows


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure: cannot describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache but
    # can never be read back without one; keep these compiles out of it.
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, sharding, *shapes):
    """Lower and compile ``fn`` for the described chip; return the HLO text."""
    args = [_spec(sharding, s, d) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _operands(part, op, *, npanels, n, dtype, batch=None):
    """(shape, dtype) of the kernel operands for one (part, op), in the
    kernels' lane layout (``PanelCSR.lane_cols`` / ``lane_vals``,
    ``PanelBCSR.vals_window``)."""
    br = 16 if dtype == jnp.bfloat16 else 8
    lead = () if batch is None else (batch,)
    meta = [((npanels,), jnp.int32), ((npanels * G,), jnp.int32)]
    b = (lead + (K, n), dtype)
    if op == "spmm":
        window = -(-npanels * G // 128) * 128
        vals = ((npanels * G,), dtype) if part == "csr" else \
            ((br, window), dtype)
        return meta + [vals, b], br
    rows = M if part == "csr" else M // br * br
    return meta + [(lead + (rows, n), dtype), b], br


def _kernel(part, op, *, br, depth, nrows=M):
    if (part, op) == ("csr", "spmm"):
        return lambda r, c, v, b: csr_panels_spmm_pallas(
            r, c, v, b, g=G, nrows=nrows, interpret=False,
            pipeline_depth=depth)
    if (part, op) == ("bcsr", "spmm"):
        return lambda r, c, v, b: bcsr_panels_spmm_pallas(
            r, c, v, b, g=G, nblocks=nrows // br, interpret=False,
            pipeline_depth=depth)
    if (part, op) == ("csr", "sdd"):
        return lambda r, c, dy, b: csr_sdd_panels_pallas(
            r, c, dy, b, g=G, interpret=False, pipeline_depth=depth)
    return lambda r, c, dy, b: bcsr_sdd_panels_pallas(
        r, c, dy, b, g=G, br=br, interpret=False, pipeline_depth=depth)


@pytest.mark.parametrize("depth", [1, 2])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("n", [32, 128])
@pytest.mark.parametrize("part,op", [("csr", "spmm"), ("bcsr", "spmm"),
                                     ("csr", "sdd"), ("bcsr", "sdd")])
def test_panel_kernel_compiles(one_chip, part, op, n, dtype, depth):
    shapes, br = _operands(part, op, npanels=P, n=n, dtype=dtype)
    hlo = _compile(_kernel(part, op, br=br, depth=depth), one_chip, *shapes)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("part", ["csr", "bcsr"])
def test_chunked_metadata_compiles(one_chip, part):
    """More panels than one call's SMEM holds: the kernel runs as a scan of
    chunk launches plus a remainder, and still compiles."""
    npanels = 2 * panels_per_call(G) + 1000
    shapes, br = _operands(part, "spmm", npanels=npanels, n=32,
                           dtype=jnp.float32)
    hlo = _compile(_kernel(part, "spmm", br=br, depth=1), one_chip, *shapes)
    assert hlo.count("tpu_custom_call") >= 2


@pytest.mark.parametrize("op", ["spmm", "sdd"])
def test_batched_kernel_compiles(one_chip, op):
    shapes, br = _operands("bcsr", op, npanels=P, n=32, dtype=jnp.float32,
                           batch=4)
    hlo = _compile(_kernel("bcsr", op, br=br, depth=1), one_chip, *shapes)
    assert "tpu_custom_call" in hlo
    shapes, br = _operands("csr", op, npanels=P, n=32, dtype=jnp.float32,
                           batch=4)
    hlo = _compile(_kernel("csr", op, br=br, depth=1), one_chip, *shapes)
    assert "tpu_custom_call" in hlo
