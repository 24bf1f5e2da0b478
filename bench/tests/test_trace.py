"""Trace reduction on a trace recorded on the chip (TPU v5 lite, opt-6.7b
at published widths, decode mix: one chunk step and one decode step) and
the compiled programs' HLO of the same run."""
import gzip
import os

import pytest

from bench import readers, trace

DATA = os.path.join(os.path.dirname(__file__), "data")


@pytest.fixture(scope="module")
def red():
    hlo = {}
    for prog in ("decode", "chunk"):
        with gzip.open(os.path.join(
                DATA, f"decode-6.7b.{prog}_hlo.txt.gz"), "rt") as f:
            hlo[prog] = f.read()
    return trace.reduce(os.path.join(DATA, "decode-6.7b.xplane.pb.gz"), hlo)


def test_window_and_busy(red):
    assert 0 < red["busy_s"] <= red["window_s"]
    assert red["window_s"] == pytest.approx(2.792132255)
    idle = readers.idle_share({"trace": red})
    assert 0 < idle < 1


def test_program_times(red):
    assert trace.module_times(red, "decode") == [pytest.approx(0.130983245)]
    assert trace.module_times(red, "chunk") == [pytest.approx(2.65161521)]


def test_kernels_by_program(red):
    count = {}
    for prog, kern, _, _, _ in red["kernels"]:
        count[prog, kern] = count.get((prog, kern), 0) + 1
    # 32 layers: six packed matmuls and one attention kernel per layer
    assert count == {("decode", "w4a8_matmul"): 192,
                     ("decode", "flash_decode_paged"): 32,
                     ("chunk", "w4a8_matmul"): 192,
                     ("chunk", "flash_prefill_paged"): 32}


def test_operand_shapes_from_the_compiled_program(red):
    calls = red["calls"]["chunk"]
    shapes = sorted(ops[2][1] for k, ops, _ in calls.values()
                    if k == "w4a8_matmul")
    assert shapes[0] == (2048, 4096) and shapes[-1] == (8192, 4096)
    x = [ops[0][1] for k, ops, _ in calls.values() if k == "w4a8_matmul"]
    assert (2048, 16384) in x and (2048, 4096) in x


def test_w4a8_roofline_share_is_a_share(red):
    ctx = {"trace": red, "device_kind": "TPU v5 lite"}
    for prog in ("decode", "chunk"):
        share = readers.kernel_share(ctx, "w4a8_matmul", prog)
        assert 0 < share < 100


def test_breakdown_is_bounded(red):
    b = trace.breakdown(red)
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert b["device_ops"][0][0].startswith("w4a8_matmul:")
    assert all(v > 0 for _, v in b["device_ops"])


def test_union_and_idle_gaps():
    assert trace._union([(0, 2), (1, 3), (5, 6)]) == 4
    red = {"ops": [("a", 10, 20), ("b", 30, 40)], "lo": 0, "hi": 50,
           "spans": [("bench.step", 0, 50), ("bench.admit", 20, 30)]}
    gaps = trace.idle_gaps(red)
    assert sorted(gaps) == sorted([("bench.step", 1e-8),
                                   ("bench.admit", 1e-8),
                                   ("bench.step", 1e-8)])
