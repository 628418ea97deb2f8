"""``bench/trace.py`` on a small trace recorded on one TPU v5e chip
(``data/v5e_small.xplane.pb``, made by ``record_trace.py``): five
rounds of a ``bench.pump`` span, a ``bench.step`` span around one
2048-square bf16 matrix product, and a ``bench.wait`` span around a
20 ms sleep, all inside ``bench.window``."""
from pathlib import Path

import pytest

from bench import trace

FIXTURE = Path(__file__).parent / "data" / "v5e_small.xplane.pb"


@pytest.fixture(scope="module")
def reduced():
    return trace.reduce(str(FIXTURE))


def test_busy_time_is_the_five_products(reduced):
    # each product ran 90.9-91.0 us on the chip (XLA Modules line)
    assert reduced["devices"] == ["/device:TPU:0"]
    assert reduced["busy_s"] == pytest.approx(5 * 90.95e-6, rel=0.01)
    assert reduced["window_s"] == pytest.approx(0.115730942)
    share = reduced["busy_s"] / reduced["window_s"]
    assert 0.003 < share < 0.005


def test_device_ops_are_named_by_program_and_instruction(reduced):
    name, secs = reduced["device_ops"][0]
    assert name == ("jit__lambda(9499260701863402228)/"
                    "convolution_multiply_fusion")
    assert secs == pytest.approx(reduced["busy_s"], rel=0.01)
    assert len(reduced["device_ops"]) <= trace.TOP


def test_idle_gaps_are_labelled_by_the_host_spans(reduced):
    gaps = dict(reduced["idle_gaps"])
    assert set(gaps) <= set(trace.LABELS) | {"host"}
    assert reduced["idle_gaps"][0][0] == "bench.wait"
    assert gaps["bench.wait"] > 5 * 0.02 * 0.95      # five 20 ms sleeps
    idle = reduced["window_s"] - reduced["busy_s"]
    assert sum(gaps.values()) == pytest.approx(idle, rel=1e-6)


def test_op_names():
    assert trace.op_name(
        "%convert.167 = bf16[16,2048,8192]{2,1,0:T(8,128)(2,1)} convert("
        "f32[16,2048,8192]{2,1,0:T(8,128)} %p__blocks____mlp____w1__.1)") \
        == ("convert.167(p__blocks____mlp____w1__.1)", "convert")
    assert trace.op_name(
        "%while.15 = (s32[]{:T(128)}, bf16[1,1,2048]{2,1,0:T(2,128)(2,1)})"
        " while((s32[]{:T(128)}) %tuple.3)")[1] == "while"
