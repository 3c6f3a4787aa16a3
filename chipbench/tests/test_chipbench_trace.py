"""The reduction from a trace to numbers, on a hand-built trace."""
import pytest

from chipbench.lib import trace as T

W = (1000.0, 2000.0)
OPS = [
    T.Op("fusion.1", 900.0, 1100.0),          # starts before the window
    T.Op("custom-call.2", 1050.0, 1200.0),    # overlaps fusion.1
    T.Op("convolution.3", 1100.0, 1150.0),    # nested in custom-call.2
    T.Op("fusion.1", 1400.0, 1500.0),
    T.Op("fusion.4", 1900.0, 2100.0),         # runs past the window
    T.Op("fusion.9", 1300.0, 1350.0, device=1),
]
HOST = [                                      # host spans, trace clock
    T.Op("window", 1000.0, 2000.0),
    T.Op("loop", 1000.0, 1950.0),
    T.Op("zlib", 1210.0, 1390.0),
    T.Op("mac", 1600.0, 1880.0),
]


def test_union_merges_overlap_and_nesting():
    assert T.union([(3, 4), (1, 2), (1.5, 2.5), (2.5, 3)]) == [(1, 4)]
    assert T.union([(0, 1), (2, 3)]) == [(0, 1), (2, 3)]


def test_busy_is_the_union_inside_the_window():
    # [1000,1200] + [1400,1500] + [1900,2000] on device 0
    assert T.busy_ns(OPS, W) == pytest.approx(200 + 100 + 100)
    assert T.busy_ns(OPS, W, device=1) == pytest.approx(50)


def test_idle_share_and_gaps():
    gs = T.gaps(OPS, W)
    assert gs == [(1200.0, 1400.0), (1500.0, 1900.0)]
    idle = 1 - T.busy_ns(OPS, W) / (W[1] - W[0])
    assert idle == pytest.approx(0.6)
    assert sum(e - s for s, e in gs) == pytest.approx(600)


def test_gaps_are_labelled_by_the_innermost_host_span():
    out = T.labelled_gaps(OPS, HOST, W)             # longest first
    assert [n.split(" @")[0] for n, _ in out] == ["mac", "zlib"]
    assert [s for _, s in out] == pytest.approx([400e-9, 200e-9])
    assert T.host_label(HOST, 1960.0) == "window"
    assert T.host_label(HOST, 5000.0) == "outside_spans"


def test_kernel_time_clips_to_the_window():
    ns, n = T.kernel_ns(OPS, lambda name: name == "fusion.1", W)
    assert (ns, n) == (100 + 100, 2)
    ns, n = T.kernel_ns(OPS, lambda name: "custom-call" in name
                        or "convolution" in name, W)
    assert (ns, n) == (150 + 50, 2)
    assert T.kernel_ns(OPS, lambda name: False, W) == (0.0, 0)


def test_top_ops_sum_by_name():
    top = dict(T.top_ops(OPS, W))
    assert top["fusion.1"] == pytest.approx(200e-9)
    assert "fusion.9" not in top                   # another device


def test_roofline_takes_the_larger_bound():
    # 1e12 ops at 1e14/s = 10 ms; 1e9 B at 1e11 B/s = 10 ms; measured 20 ms
    assert T.roofline_pct(1e12, 1e9, 0.02, 1e14, 1e11) == pytest.approx(50)
    assert T.roofline_pct(0, 2e9, 0.04, 1e14, 1e11) == pytest.approx(50)
    assert T.roofline_pct(0, 0, 1.0, 1e14, 1e11) is None
    assert T.roofline_pct(1, 1, 0.0, 1e14, 1e11) is None


def test_load_reads_the_benchmark_spans_of_a_recorded_trace():
    """The device part's spans land on the trace's clock, inside the
    profiled stretch, in the order and length the host recorded them."""
    import jax
    import jax.numpy as jnp
    from chipbench.lib import harness

    f = jax.jit(lambda a: (a @ a).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    tracer = harness.Tracer([], spans=False)
    try:
        tracer.begin("device")
        with tracer.rec.span("window"):
            with tracer.rec.span("loop"):
                f(x).block_until_ready()
        tracer.end("device")
        tr = T.load(tracer.dir)
    finally:
        tracer.close()
    assert tr.start_ns is not None
    assert [s.name for s in tracer.rec.spans] == ["loop", "window"]
    assert tracer.rec.spans[0].parent == "window"
    loop, win = (tracer.to_trace_ns(t, tr.start_ns) for t in
                 (tracer.rec.spans[0].t0, tracer.rec.spans[1].t0))
    assert 0 <= win <= loop < 5e9
    span = tracer.rec.spans[0]
    assert (tracer.to_trace_ns(span.t1, tr.start_ns)
            - tracer.to_trace_ns(span.t0, tr.start_ns)) == pytest.approx(
                span.seconds * 1e9)


# operation names as a TPU v5e trace of the split2 cell records them
RECORDED = {
    "window": '%_lambda_.5 = f32[1,140,203,96]{3,2,1,0:T(8,128)S(1)} custom-call(f32[1,140,203,288]{3,2,1,0:T(8,128)S(1)} %bitcast_add_fusion, f32[1,140,203,288]{3,2,1,0:T(8,128)S(1)} %bitcast_add_fusion, f32[3,64,64]{2,1,0:T(8,128)S(1)} %pad.8, s8[20,29,64,64]{3,2,1,0:T(8,128)(4,1)S(1)} %get-tuple-element.25), custom_call_target="tpu_custom_call", operand_layout_constraints={f32[1,140,203,288]{3,2,1,0}, f32[1,140,203,288]{3,2,1,0}, f32[3,64,64]{2,1,0}, s8[20,29,64,64]{3,2,1,0}}, frontend_attributes={kernel_metadata={}}',  # noqa: E501
    "encode": '%encode.1 = (s8[35776,128]{1,0:T(8,128)(4,1)}, f32[559,1,1]{2,1,0:T(1,128)S(1)}) custom-call(f32[35776,128]{1,0:T(8,128)S(1)} %fusion), custom_call_target="tpu_custom_call", operand_layout_constraints={f32[35776,128]{1,0}}, frontend_attributes={kernel_metadata={}}',  # noqa: E501
    "decode": '%decode.1 = f32[35776,128]{1,0:T(8,128)S(1)} custom-call(s8[35776,128]{1,0:T(8,128)(4,1)} %bitcast.4, f32[559,1,1]{2,1,0:T(1,128)S(1)} %reshape.2), custom_call_target="tpu_custom_call", operand_layout_constraints={s8[35776,128]{1,0}, f32[559,1,1]{2,1,0}}, frontend_attributes={kernel_metadata={}}',  # noqa: E501
    "fusion": '%fusion.241 = f32[1,136,200,96]{3,0,2,1:T(1,128)S(1)} fusion(bf16[1,544,800,3]{3,0,2,1:T(2,128)(2,1)} %copy.130, f32[4,4,3,96]{3,1,2,0:T(4,128)S(1)} %copy-done.20), kind=kOutput, calls=%fused_computation.217',  # noqa: E501
}


def test_kernels_are_told_apart_by_their_recorded_shapes():
    assert [k for k, n in RECORDED.items() if T.is_window_attn(n)] == \
        ["window"]
    assert [k for k, n in RECORDED.items() if T.is_codec(n)] == \
        ["encode", "decode"]
    ops = [T.Op(RECORDED[k], s, e) for k, s, e in
           [("window", 0, 40), ("encode", 50, 60), ("decode", 70, 75),
            ("fusion", 80, 100), ("window", 120, 150)]]
    assert T.kernel_ns(ops, T.is_window_attn, (0, 140)) == (60.0, 2)
    assert T.kernel_ns(ops, T.is_codec, (0, 140)) == (15.0, 2)
