"""Kernel time by part, from ops named by their HLO instruction."""
import types

import bench_tiny  # noqa: F401  (puts the checkout on sys.path)
import pytest

from bench import kernel_parts, tracing


def hlo(inst, target=True):
    """An op named by its HLO text, as a TPU's op line names it."""
    call = ('custom-call(s32[1]{0} %p), custom_call_target="tpu_custom_call"'
            if target else "fusion(f32[8]{0} %p), kind=kLoop")
    return f"%{inst} = f32[217920,32]{{1,0:T(8,128)}} {call}"


def ev(kind, name, start, end):
    return {"kind": kind, "plane": "/device:TPU:0" if kind == "device"
            else "/host:CPU", "name": name, "start_ns": float(start),
            "end_ns": float(end), "stats": {}}


def two_steps():
    """Two steps: a chunk loop around two BCSR kernel launches, then the
    CSR kernel, an unnamed kernel, and the glue around them."""
    return [
        ev("host", "bench.step", 0, 100), ev("host", "bench.step", 100, 200),
        ev("device", hlo("while.4", target=False), 5, 60),
        ev("device", hlo("loops_bcsr_spmm.3"), 10, 30),
        ev("device", hlo("loops_bcsr_spmm.3"), 32, 55),
        ev("device", hlo("loops_csr_spmm.1"), 60, 90),
        ev("device", hlo("fusion.2", target=False), 120, 130),
        ev("device", hlo("loops_bcsr_spmm.5"), 130, 170),
        ev("device", hlo("closed_call.9"), 170, 180),
        ev("device", hlo("loops_csr_spmm.1"), 190, 230),   # past the window
    ]


@pytest.mark.parametrize("name,part", [
    (hlo("loops_bcsr_spmm.3"), "bcsr"),
    (hlo("loops_csr_spmm.12"), "csr"),
    (hlo("loops_csr_sdd.2"), "csr"),
    ("loops_bcsr_spmm.7", None),        # not a kernel by name or statistic
    (hlo("closed_call.27"), None),
    # A fusion taking a kernel's output is not that kernel.
    ("%fusion.4 = f32[8]{0} fusion(f32[8]{0} %loops_csr_spmm.1)", None),
])
def test_a_kernel_is_put_in_its_part_by_instruction_name(name, part):
    assert kernel_parts.part_of(ev("device", name, 0, 1)) == part


def test_parts_split_kernel_time_and_sum_to_it_when_all_are_named():
    events = two_steps()
    parts = kernel_parts.part_seconds(events)
    assert parts["bcsr"] == pytest.approx((20 + 23 + 40) * 1e-9)
    assert parts["csr"] == pytest.approx((30 + 10) * 1e-9)  # clipped at 200
    kernel_s = tracing.reduce(events)["kernel_s"]
    assert kernel_s == pytest.approx(sum(parts.values()) + 10e-9)
    named = [e for e in events if "closed_call" not in e["name"]]
    assert sum(kernel_parts.part_seconds(named).values()) == pytest.approx(
        tracing.reduce(named)["kernel_s"])


def test_unnamed_kernels_give_no_part():
    events = [ev("host", "bench.step", 0, 100),
              ev("device", hlo("closed_call.27"), 10, 90)]
    assert kernel_parts.part_seconds(events) == {}


def test_reader_finds_nothing_without_a_trace_on_disk(tmp_path):
    metric = tmp_path / "bench" / "metrics" / "bcsr_kernel_ms.py"
    ctx = types.SimpleNamespace(cell="nothing-traced",
                                trace={"steps": 3, "kernel_s": 1.0})
    assert kernel_parts.read(ctx, "bcsr", str(metric)) is None
    assert kernel_parts.read(types.SimpleNamespace(cell="x", trace=None),
                             "bcsr", str(metric)) is None
