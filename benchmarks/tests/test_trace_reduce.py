"""The trace reduction: busy union, class map, self time under a ``while``,
launch count, gap attribution to the harness's host spans; and, on a trace
recorded here by the CPU profiler, that host spans are found. (A recorded TPU
trace of a real step is some megabytes; the repo keeps none. The device side
is exercised on hand-made events of the shape ``read_planes`` returns.)"""

import jax
import jax.numpy as jnp

from benchmarks import tracing

US = 1_000


def _planes():
    ops = [
        ("%while.1", 0, 1000 * US),                  # spans its body
        ("%fusion.7", 0, 300 * US),
        ("%scatter.3 = f32[8]", 300 * US, 500 * US),
        ("%gather.2", 500 * US, 600 * US),
        # 600..900 idle, inside the while
        ("%convolution.4", 900 * US, 1000 * US),
        ("%copy.9", 1500 * US, 1600 * US),           # after a 500 us hole
    ]
    modules = [("jit_step", 0, 1000 * US), ("jit_step", 1500 * US, 1600 * US)]
    spans = [("epoch", 0, 2000 * US), ("loader_next", 1000 * US, 1400 * US),
             ("dispatch", 1400 * US, 1500 * US)]
    return {"/device:TPU:0": {"ops": ops, "modules": modules},
            "/device:TPU:1": {"ops": [], "modules": []}}, spans


def test_union_and_self_times():
    assert tracing.union_ns([(0, 10), (5, 20), (30, 40)]) == 30
    st = dict(tracing.self_times([("w", 0, 100), ("a", 0, 30), ("b", 30, 60)]))
    assert st == {"w": 40, "a": 30, "b": 30}


def test_shape_rules_find_fused_scatters_and_gathers():
    classes = tracing.load_classes({"N": [113144], "E": [1640064]})
    seg = ("%fusion.46 = bf16[113144,64]{1,0:T(8,128)(2,1)S(1)} fusion(s32[1640064]{0:T(1024)} "
           "%get-tuple-element.2433, bf16[1640064,64]{1,0:T(8,128)(2,1)} %copy.1097, s32[1640448]{0} %x), kind=kCustom")
    packed = ("%fusion.52 = f32[68,113144]{0,1:T(8,128)} fusion(s32[1640064]{0:T(1024)} %b, "
              "f32[1640064,68]{1,0:T(8,128)} %copy.983, f32[]{:T(128)} %c)")
    gat = ("%fusion.33 = f32[1640064,68]{1,0:T(8,128)} fusion(f32[1,113144,68]{2,1,0:T(8,128)S(1)} "
           "%copy.1040, s32[1640064]{0:T(1024)S(1)} %custom-call.133), kind=kCustom")
    mlp = "%fusion.9 = bf16[1640064,64]{1,0} fusion(bf16[1640064,64]{1,0} %a, bf16[64,64]{1,0} %w)"
    assert tracing.classify(seg, classes) == "scatter"
    assert tracing.classify(packed, classes) == "scatter"
    assert tracing.classify(gat, classes) == "gather"
    assert tracing.classify(mlp, classes) == "fusion"
    assert tracing.op_label(seg) == "fusion.46"
    # a batch of graphs shows its axes flattened: 250 x 9,984 edges, 250 x 104 nodes
    batch = tracing.load_classes({"N": [104, 26000], "E": [9984, 2496000]})
    flat = ("%fusion.2298 = f32[2496000,3]{1,0:T(8,128)} fusion(f32[250,104,3]{2,1,0:T(8,128)} "
            "%copy.4901, s32[2496000]{0:T(1024)} %reshape.2114), kind=kCustom, calls=%fused")
    assert tracing.classify(flat, batch) == "gather"
    # without the cell's sizes the rules are off and the opcode decides
    assert tracing.classify(seg, tracing.load_classes()) == "fusion"


def test_class_map():
    classes = tracing.load_classes()
    assert tracing.classify("%scatter.3 = f32[8]", classes) == "scatter"
    assert tracing.classify("gather.12", classes) == "gather"
    assert tracing.classify("%fusion.7", classes) == "fusion"
    assert tracing.classify("%convolution.4", classes) == "matmul"
    assert tracing.classify("%all-reduce.1", classes) == "collective"
    assert tracing.classify("%while.1", classes) == "control"
    assert tracing.classify("%mystery", classes) == "other"


def test_reduce_planes():
    devices, spans = _planes()
    r = tracing.reduce_planes(devices, spans, chips=1)
    assert abs(r["busy_s"] - 1100e-6) < 1e-12           # while covers 0..1000, copy 100
    assert abs(r["class_s"]["scatter"] - 200e-6) < 1e-12
    assert abs(r["class_s"]["gather"] - 100e-6) < 1e-12
    assert abs(r["class_s"]["fusion"] - 300e-6) < 1e-12
    assert "control" not in r["class_s"]
    assert r["launches"] == 2
    assert r["ops"][0][0] == "fusion:fusion.7"
    gaps = dict(r["gaps"])
    # the 500 us hole's midpoint (1250 us) lies in loader_next
    assert abs(gaps["loader_next"] - 500e-6) < 1e-12


def test_gap_outside_any_span():
    g = tracing.attribute([(0, 100_000)], [("epoch", 200_000, 300_000)])
    assert g == {"outside_spans": 1e-4}


def test_host_spans_in_a_recorded_trace(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench/dispatch"):
        jnp.ones((64, 64)).sum().block_until_ready()
    jax.profiler.stop_trace()
    devices, spans = tracing.read_planes(tracing.find_xplane(str(tmp_path)))
    assert [n for n, _, _ in spans] == ["dispatch"]
    assert devices == {}                                  # no TPU plane on the CPU
    assert tracing.reduce_planes(devices, spans, 1)["busy_s"] == 0.0
