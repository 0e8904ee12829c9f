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
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.formats import csr_from_dense
from repro.core.spmm import loops_spmm, plan_and_convert
from repro.kernels.bcsr_spmm import bcsr_panels_spmm_pallas, steps_per_call
from repro.kernels.csr_spmm import csr_panels_spmm_pallas
from repro.kernels.panel_common import panels_per_call, window_steps
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


def _block_rows(npanels, nblocks):
    """Host ``panel_rows`` spreading ``npanels`` over ``nblocks`` block
    rows: the BCSR SpMM kernel builds its step table from them, so they
    are data, not an operand."""
    return (np.arange(npanels) * nblocks // npanels).astype(np.int32)


def _kernel(part, op, *, br, nrows=M, npanels=P):
    if (part, op) == ("csr", "spmm"):
        return lambda r, c, v, b: csr_panels_spmm_pallas(
            r, c, v, b, g=G, nrows=nrows, interpret=False)
    if (part, op) == ("bcsr", "spmm"):
        rows = _block_rows(npanels, nrows // br)
        return lambda r, c, v, b: bcsr_panels_spmm_pallas(
            rows, c, v, b, g=G, nblocks=nrows // br, interpret=False)
    if (part, op) == ("csr", "sdd"):
        return lambda r, c, dy, b: csr_sdd_panels_pallas(
            r, c, dy, b, g=G, interpret=False)
    return lambda r, c, dy, b: bcsr_sdd_panels_pallas(
        r, c, dy, b, g=G, br=br, interpret=False)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("n", [32, 40, 128, 256])
@pytest.mark.parametrize("part,op", [("csr", "spmm"), ("bcsr", "spmm"),
                                     ("csr", "sdd"), ("bcsr", "sdd")])
def test_panel_kernel_compiles(one_chip, part, op, n, dtype):
    """Each kernel at the widths the cells run (32; a GCN's 40 and 256)
    and at one lane tile."""
    shapes, br = _operands(part, op, npanels=P, n=n, dtype=dtype)
    hlo = _compile(_kernel(part, op, br=br), one_chip, *shapes)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("part", ["csr", "bcsr"])
def test_chunked_metadata_compiles(one_chip, part):
    """More panels than one call's SMEM holds: the kernel runs as a scan of
    chunk launches plus a remainder, and still compiles."""
    npanels = 2 * panels_per_call(G) + 1000
    shapes, br = _operands(part, "spmm", npanels=npanels, n=32,
                           dtype=jnp.float32)
    hlo = _compile(_kernel(part, "spmm", br=br, npanels=npanels),
                   one_chip, *shapes)
    assert hlo.count("tpu_custom_call") >= 2


@pytest.mark.parametrize("layout", ["chunked", "batched"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("n", [32, 128, 256])
def test_bcsr_window_steps_compile(one_chip, n, dtype, layout):
    """The BCSR kernel's window steps and manual DMA gather: B rows from
    HBM into a two-slot VMEM stage, at N below and at one lane tile, in
    more calls than one SMEM holds or over a batched operand."""
    br = 16 if dtype == jnp.bfloat16 else 8
    npanels = 2 * panels_per_call(G) + 1000 if layout == "chunked" else P
    rows = _block_rows(npanels, M // br)
    steps = window_steps(rows, G)
    assert len(steps[0]) > steps_per_call(steps[1])[0] or layout != "chunked"
    shapes, _ = _operands("bcsr", "spmm", npanels=npanels, n=n, dtype=dtype,
                          batch=4 if layout == "batched" else None)
    hlo = _compile(lambda r, c, v, b: bcsr_panels_spmm_pallas(
        rows, c, v, b, g=G, nblocks=M // br, interpret=False, steps=steps),
        one_chip, *shapes)
    assert hlo.count("tpu_custom_call") >= (2 if layout == "chunked" else 1)


@pytest.mark.parametrize("op", ["spmm", "sdd"])
def test_batched_kernel_compiles(one_chip, op):
    shapes, br = _operands("bcsr", op, npanels=P, n=32, dtype=jnp.float32,
                           batch=4)
    hlo = _compile(_kernel("bcsr", op, br=br), one_chip, *shapes)
    assert "tpu_custom_call" in hlo
    shapes, br = _operands("csr", op, npanels=P, n=32, dtype=jnp.float32,
                           batch=4)
    hlo = _compile(_kernel("csr", op, br=br), one_chip, *shapes)
    assert "tpu_custom_call" in hlo


_CUSTOM_CALL = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = .*custom-call\(.*"
                          r"custom_call_target=\"tpu_custom_call\"")


@pytest.mark.parametrize("n", [256, 40])
def test_fused_kernels_carry_their_names(one_chip, n):
    """The fused ``loops_spmm`` names every kernel instruction after its
    part, so that a device trace tells the CSR kernel from the BCSR one;
    the BCSR part here has more panels than one call's SMEM holds, so its
    chunk loop and remainder are named too.  At a GCN's widths, fp32."""
    rng = np.random.default_rng(0)
    a = ((rng.random((8192, 256)) < 0.05)
         * rng.standard_normal((8192, 256))).astype(np.float32)
    fmt, _ = plan_and_convert(csr_from_dense(a))
    assert fmt.bcsr_panels.npanels > panels_per_call(fmt.panel_g)
    hlo = _compile(lambda b: loops_spmm(fmt, b, backend="pallas"), one_chip,
                   ((256, n), jnp.float32))
    names = [m.group(1) for m in map(_CUSTOM_CALL.match, hlo.splitlines())
             if m]
    assert len(names) == hlo.count('custom_call_target="tpu_custom_call"')
    parts = {re.sub(r"\.\d+$", "", n) for n in names}
    assert parts == {"loops_csr_spmm", "loops_bcsr_spmm"}
    assert sum(n.startswith("loops_bcsr_spmm") for n in names) >= 2


@pytest.mark.parametrize("n", [256, 40])
def test_backward_kernels_carry_transposed_names(one_chip, n):
    """The gradient of ``loops_spmm`` runs the forward kernels on A and the
    same kernels on Aᵀ, named ``loops_csr_spmm_t``/``loops_bcsr_spmm_t``,
    at a GCN's widths: 256 (two 128-lane pieces of the BCSR gather) and 40
    (the lane-dense view at a width that does not divide 128), fp32."""
    rng = np.random.default_rng(0)
    a = ((rng.random((4096, 4096)) < 0.002)
         * rng.standard_normal((4096, 4096))).astype(np.float32)
    fmt, _ = plan_and_convert(csr_from_dense(a))
    assert 0 < fmt.r_boundary < fmt.nrows
    hlo = _compile(jax.grad(lambda b: jnp.sum(
        loops_spmm(fmt, b, backend="pallas") ** 2)), one_chip,
        ((4096, n), jnp.float32))
    names = [m.group(1) for m in map(_CUSTOM_CALL.match, hlo.splitlines())
             if m]
    assert len(names) == hlo.count('custom_call_target="tpu_custom_call"')
    parts = {re.sub(r"\.\d+$", "", x) for x in names}
    assert parts == {"loops_csr_spmm", "loops_bcsr_spmm", "loops_csr_spmm_t",
                     "loops_bcsr_spmm_t"}
