"""The reduction from trace events to numbers, on a made-up list."""

from benchmark import kernel_work
from benchmark import trace_reduce as tr

OPS = [("fusion.1", 0.0, 10.0), ("lrn_fwd.3", 5.0, 10.0),     # overlap
       ("convolution.2", 20.0, 5.0), ("lrn_bwd", 40.0, 10.0),
       ("fusion.1", 50.0, 10.0)]


def test_busy_is_the_union_not_the_sum():
    assert tr.busy_ns(OPS) == 15.0 + 5.0 + 20.0
    assert tr.busy_ns([]) == 0.0
    assert tr.busy_ns([("a", 0.0, 10.0), ("b", 2.0, 3.0)]) == 10.0


def test_kernel_filter_by_name():
    assert tr.kernel_ns(OPS, ("lrn_fwd", "lrn_bwd")) == 20.0
    assert tr.kernel_ns(OPS, ("flash",)) == 0.0
    assert [e[0] for e in tr.named(OPS, "lrn_fwd")] == ["lrn_fwd.3"]


def test_an_operation_is_named_by_what_stands_before_the_equals_sign():
    kernel = ("%jvp_lrn_fwd_.2 = bf16[2048,96,729]{2,1,0:T(8,128)(2,1)} "
              "custom-call(bf16[2048,96,729]{2,1,0} %copy.161), "
              'custom_call_target="tpu_custom_call"')
    reader = ("%fusion.9 = (bf16[96]{0:T(256)}, bf16[8,4]{1,0}) "
              "fusion(bf16[2048,96,729]{2,1,0} %jvp_lrn_fwd_.2)")
    assert tr.split_name(kernel) == (
        "jvp_lrn_fwd_.2", "jvp_lrn_fwd_.2 bf16[2048,96,729]")
    assert tr.split_name(reader) == (
        "fusion.9", "fusion.9 (bf16[96], bf16[8,4])")
    assert tr.split_name("bench.window") == ("bench.window", "bench.window")


def test_gaps_and_clip():
    assert tr.gaps(OPS, 0.0, 70.0) == [(15.0, 20.0), (25.0, 40.0),
                                       (60.0, 70.0)]
    assert tr.clip(OPS, 8.0, 22.0) == [("fusion.1", 8.0, 2.0),
                                       ("lrn_fwd.3", 8.0, 7.0),
                                       ("convolution.2", 20.0, 2.0)]


def test_top_ops_sums_by_name():
    assert tr.top_ops(OPS, 2) == [("fusion.1", 20e-9), ("lrn_fwd.3", 10e-9)]


def test_idle_gaps_go_to_the_innermost_host_span():
    host = [("bench.window", 0.0, 70.0), ("bench.dispatch", 14.0, 8.0),
            ("bench.backpressure", 24.0, 30.0)]
    got = dict(tr.idle_by_host(OPS, host, 0.0, 70.0))
    assert got == {"bench.dispatch": 5e-9, "bench.backpressure": 15e-9,
                   "host_other": 10e-9}
    assert tr.window_of(tr.Trace({"d": OPS}, host)) == (0.0, 70.0)
    assert tr.window_of(tr.Trace({"d": OPS}, [])) == (0.0, 60.0)


def test_lrn_bytes_at_alexnets_two_shapes():
    # bf16, batch 2048: 96x27x27 after pool1, 256x13x13 after pool2
    a, b = (2048, 96, 27, 27), (2048, 256, 13, 13)
    assert kernel_work.lrn_fwd_bytes(a, 2) == 2 * 2048 * 69984 * 2
    assert kernel_work.lrn_bwd_bytes(a, 2) == 3 * 2048 * 69984 * 2
    assert kernel_work.lrn_fwd_bytes(b, 2) == 2 * 2048 * 43264 * 2
    assert kernel_work.lrn_bwd_bytes(b, 2) == 3 * 2048 * 43264 * 2
