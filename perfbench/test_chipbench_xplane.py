"""Trace reduction on a synthetic trace whose answers are known."""
import pytest

import xplane
from xplane import Module, Op, Trace


def _trace():
    # chip 0: a decode module [0, 100) with the decode kernel and a
    # matmul, idle [100, 150), a prefill module [150, 300), idle to 400.
    ops = [Op("paged_decode_attention", 0, 30), Op("fusion", 20, 40),
           Op("fusion", 70, 30), Op("paged_prefill_attention", 150, 50),
           Op("convolution", 200, 100)]
    mods = [Module("jit_wrapper", 0, 100), Module("jit_wrapper", 150, 150)]
    host = [("PjitFunction(wrapper)", 95, 70), ("idle loop", 0, 400)]
    return Trace(1, [ops], [mods], host)


def test_busy_is_the_union_of_op_intervals():
    assert xplane.busy_s(_trace()) == pytest.approx(240e-9)


def test_modules_are_named_by_the_kernels_they_run():
    tr = _trace()
    xplane.assign_ops(tr, {"paged_decode_attention": "decode",
                           "paged_prefill_attention": "prefill_chunk"})
    assert [m.kind for m in tr.modules[0]] == ["decode", "prefill_chunk"]
    assert xplane.module_time_s(tr, "decode") == pytest.approx(90e-9)
    assert xplane.module_time_s(tr, "prefill_chunk") == pytest.approx(
        150e-9)


def test_op_time_and_top_ops():
    tr = _trace()
    t = xplane.op_time_s(tr)
    assert t["fusion"] == pytest.approx(70e-9)
    assert xplane.top_ops(tr, top=1) == [("convolution",
                                          pytest.approx(100e-9))]


def test_idle_gaps_carry_programs_around_and_host_event():
    tr = _trace()
    xplane.assign_ops(tr, {"paged_decode_attention": "decode",
                           "paged_prefill_attention": "prefill_chunk"})
    gaps = xplane.idle_gaps(tr)
    assert gaps == [("decode->prefill_chunk: PjitFunction(wrapper)",
                     pytest.approx(50e-9)),
                    ("in decode: idle loop", pytest.approx(10e-9))]


def test_op_label_finds_kernel_names_in_metadata():
    kernels = ["paged_decode_attention", "paged_cache_update"]
    assert xplane.op_label(
        "custom-call.12", {"tf_op": "jit(wrapper)/paged_cache_update/"
                                    "pallas_call"}, kernels) \
        == "paged_cache_update"
    assert xplane.op_label("fusion.3", {}, kernels) == "fusion"


def test_two_chips_average_busy():
    tr = _trace()
    tr2 = Trace(2, tr.ops + [[Op("fusion", 0, 50)]],
                tr.modules + [[]], tr.host)
    assert xplane.busy_s(tr2) == pytest.approx((240 + 50) / 2 * 1e-9)
