"""The reduction on intervals worked by hand, and on a small trace recorded
on the v5e (``data/tiny_v5e.xplane.pb``: four calls of a jitted
``tiny_step`` of three matmul+tanh fusions, each call inside a
``bench.step`` annotation)."""

import os

import pytest

from benchmarks.harness import readers, trace_reduce

TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "tiny_v5e.xplane.pb")


def test_op_names_are_cut_from_the_hlo_line():
    line = ("%paged_decode.31 = bf16[32,8,4,128]{3,2,1,0:T(4,128)(2,1)S(1)} "
            "custom-call(s32[32,16]{1,0} %copy-done.78)")
    assert trace_reduce.op_name(line) == "paged_decode"
    assert trace_reduce.op_name("%slice_bitcast_fusion.32.remat10 = (bf16"
                                ) == "slice_bitcast_fusion"
    assert trace_reduce.op_name("%fusion.2.remat2 = x") == "fusion"
    assert trace_reduce.op_name("copy-start") == "copy-start"


def test_union_gaps_and_self_time_by_hand():
    # a loop [0, 100] holding two children, then a gap, then one op
    events = sorted([(0.0, 100.0, "while"), (10.0, 40.0, "a"),
                     (50.0, 90.0, "b"), (150.0, 200.0, "a")],
                    key=lambda e: (e[0], -e[1]))
    busy, gaps = trace_reduce.union_seconds(events)
    assert busy == pytest.approx(150e-9)
    assert gaps == [(100.0, 150.0)]
    totals, counts = trace_reduce.self_seconds(events)
    assert totals["while"] == pytest.approx(30e-9)
    assert totals["a"] == pytest.approx(80e-9)
    assert totals["b"] == pytest.approx(40e-9)
    assert counts == {"while": 1, "a": 2, "b": 1}
    host = [(90.0, 160.0, "np.asarray", "t"), (0.0, 1000.0, "serve", "t"),
            (120.0, 125.0, "short", "t")]
    assert trace_reduce.attribute_gap((100.0, 150.0), host) == "np.asarray"


def test_the_recorded_trace():
    summary = trace_reduce.reduce(trace_reduce.load(TRACE), chips=1)
    assert summary["device_planes"] == ["/device:TPU:0"]
    assert summary["op_counts"]["convolution_tanh_fusion"] == 12 \
        or summary["op_counts"]["convolution_tanh_fusion"] == 8
    assert 0 < summary["busy_s"] < summary["window_s"] < 0.05
    # four short steps with the host between them: the device idles most
    assert summary["busy_s"] / summary["window_s"] < 0.01
    assert summary["device_ops"][0][0] == "convolution_tanh_fusion"
    assert summary["idle_gaps"] and all(
        not name.startswith("bench.") for name, _s in summary["idle_gaps"])
    seconds, count = trace_reduce.matching(summary, "tanh")
    assert count == summary["op_counts"]["convolution_tanh_fusion"]
    assert seconds == pytest.approx(
        summary["op_seconds"]["convolution_tanh_fusion"])


def test_readers_return_nothing_where_there_is_nothing_to_read():
    summary = trace_reduce.reduce(trace_reduce.load(TRACE), chips=1)
    ctx = {"trace": summary, "window_s": 1.0, "finished": [], "chips": 1,
           "fields": {}, "peak": {"bf16_flops_per_s": 197e12,
                                  "hbm_bytes_per_s": 819e9}}
    assert readers.kernel_roofline(ctx, pattern="paged_decode",
                                   cost="paged_decode") is None
    assert readers.op_share(ctx, pattern="paged_decode") is None
    assert readers.op_share(ctx, pattern="tanh") > 50
    assert 99 < readers.device_idle(ctx) < 100
    assert readers.device_idle({"trace": None}) is None
    assert readers.window_mfu(dict(ctx), cost="serve_requests") is None
    assert readers.engine_stat({"engine_stats": {}}, key="x") is None
    assert readers.request_overhead({"finished": []}) is None
    assert readers.phase_share({"finished": []}, phases=["prefill"]) is None
    assert readers.phase_percentile({"finished": []}, exclude=[]) is None
    requests = [{"timing": {"wall_s": 5.0, "phases": {
        "prefill": 0.01 * i, "decode_active": 4.0, "decode_stall": 1.0}}}
        for i in range(1, 101)]
    assert readers.phase_percentile(
        {"finished": requests}, exclude=["decode_active", "decode_stall"]
    ) == pytest.approx(950.0)
    assert readers.phase_share({"finished": requests},
                               phases=["decode_stall"]) == pytest.approx(20)
