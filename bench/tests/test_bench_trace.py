"""The trace reduction, on a small trace recorded on one TPU v5e chip: two
rounds of a flash-attention forward and gradient (1 x 1024, 4 query over
2 KV heads of 64) and a 2048 x 2048 bf16 matmul, each inside a host span
(``bench.step``, ``bench.matmul``).  The expected numbers are read off the
trace's events by hand."""
from pathlib import Path

import pytest

import trace
from metric_util import FLASH_KERNELS

DATA = Path(__file__).resolve().parent / "data" / "flash_small.xplane.pb"


@pytest.fixture(scope="module")
def tr():
    return trace.load(str(DATA))


def test_planes_and_window(tr):
    assert tr.devices() == [0]
    # no bench.traced span: the window runs from the first span's start
    # to the last one's end
    assert tr.window == (171684514.0, 186351394.0)
    assert [s.name for s in tr.spans] == ["bench.step", "bench.matmul"] * 2
    assert len(tr.ops[0]) == 40 and len(tr.modules[0]) == 4


def test_program_runs_by_name(tr):
    # the first gradient program ran on the device before its host span
    # opened (host and device clocks lie ~0.5 ms apart), so it falls
    # outside the window
    assert len(tr.module_runs(r"^jit_loss\(")) == 1
    assert len(tr.module_runs(r"^jit__lambda\(")) == 2
    assert tr.matches(r"^jit_loss\(", tr.modules[0][0])


def test_flash_kernel_time(tr):
    # forward 61,618 ns, backward 32,341 + 65,131 ns in the second round
    w0, w1 = tr.window
    assert tr.kernel_time_s(FLASH_KERNELS, w0, w1) == pytest.approx(
        (61618 + 32341 + 65131) / 1e9, abs=3e-9)
    kernels = {e.name for e in tr.ops[0] if e.kernel}
    assert kernels == {"%jvp_jit__flash_attention_vjp__.1",
                       "%transpose_jvp_jit__flash_attention_vjp___.2",
                       "%transpose_jvp_jit__flash_attention_vjp___.3"}


def test_busy_idle_and_breakdown(tr):
    busy = tr.busy_s()
    # the two matmul fusions (~91.5 us each) and the second gradient
    # program (~169 us) are nearly all of it
    assert 0.00035 < busy < 0.00045
    assert busy < tr.window_s
    top = tr.top_ops(3)
    assert top[0][0] == "%fusion"
    assert top[0][1] == pytest.approx((91658 + 91483) / 1e9, abs=2e-9)
    gaps = tr.labelled_gaps(2)
    # the longest idle stretch lies between the rounds, where the host
    # ran none of the benchmark's spans
    assert gaps[0][0] == "host" and gaps[0][1] > 0.009
