import json
import types
from pathlib import Path

import pytest

import spec
import tracereduce as tr
from tracereduce import Op, Trace

KERNEL = '%k = f32[8] custom-call(), custom_call_target="tpu_custom_call"'
# Two chips, a 1000 ns window. Device 0: two fused ops that overlap, a
# Pallas call, an XLA custom call, and an op that starts before the window;
# device 1: one Pallas call.
OPS = [
    Op(0, "%fusion.1 = f32[8] fusion()", 50.0, 100.0),
    Op(0, "%fusion.2 = f32[8] fusion()", 120.0, 80.0),
    Op(0, KERNEL, 400.0, 300.0),
    Op(0, '%c = u64[2] custom-call(), custom_call_target="X64Combine"',
       700.0, 0.0),
    Op(0, "%copy.3 = f32[8] copy()", -100.0, 150.0),
    Op(1, KERNEL, 0.0, 500.0),
]
RECORDED = Path(__file__).resolve().parent / "testdata" / \
    "gcn_rsc_window_60ms.json"
HOST = [("bench.window", 0.0, 1000.0)]


def small_trace():
    t = Trace(ops=list(OPS), host=list(HOST))
    lo, hi = t.window("bench.window")
    return t.clip(lo, hi), lo, hi


def test_busy_union_and_idle():
    t, lo, hi = small_trace()
    # device 0 busy: [0, 200) from copy.3 + fusions, [400, 700) body
    assert tr.busy_ns(t, 0, lo, hi) == 500.0
    assert tr.busy_ns(t, 1, lo, hi) == 500.0
    assert tr.idle_gaps(t, 0, lo, hi) == [(200.0, 400.0), (700.0, 1000.0)]


def test_custom_call_time_and_top_ops():
    t, _, _ = small_trace()
    assert tr.op_seconds(t, tr.is_pallas) == pytest.approx(800e-9)
    assert tr.top_ops(t, 2) == [[KERNEL, pytest.approx(800e-9)],
                                ["%fusion.1 = f32[8] fusion()",
                                 pytest.approx(100e-9)]]
    assert tr.short_name("%x = " + "f" * 200).endswith("...")


def test_gaps_are_labelled_by_the_innermost_open_span():
    t, lo, hi = small_trace()
    spans = [("step", 150.0, 900.0), ("plan", 190.0, 420.0)]
    gaps = tr.top_gaps(t, 0, lo, hi, spans)
    assert tr.label_gap((950.0, 990.0), spans) == "outside_spans"
    assert gaps == [["step", pytest.approx(300e-9)],
                    ["plan", pytest.approx(200e-9)]]


def test_json_round_trip(tmp_path):
    t = Trace(ops=list(OPS), host=list(HOST))
    p = tmp_path / "t.json"
    p.write_text(json.dumps({"ops": [[o.device, o.name, o.start_ns,
                                       o.dur_ns] for o in OPS],
                             "host": HOST}))
    back = tr.load_json(str(p))
    assert back.ops == t.ops and back.host == [tuple(h) for h in HOST]


def test_recorded_chip_trace():
    """60 ms of a traced gcn-reddit-rsc window on a TPU v5 lite."""
    t = tr.load_json(str(RECORDED))
    lo, hi = t.window("bench.window")
    t = t.clip(lo, hi)
    # an independent busy count: sweep the start and end events (ops of
    # no duration, such as X64Combine at this clock, add nothing)
    timed = [o for o in t.ops if o.dur_ns > 0]
    events = sorted([(o.start_ns, 1) for o in timed]
                    + [(o.end_ns, -1) for o in timed])
    busy, depth, since = 0.0, 0, None
    for x, step in events:
        if depth == 0 and step == 1:
            since = x
        depth += step
        if depth == 0:
            busy += x - since
    assert tr.busy_ns(t, 0, lo, hi) == pytest.approx(busy)
    gaps = tr.idle_gaps(t, 0, lo, hi)
    assert sum(e - s for s, e in gaps) == pytest.approx(hi - lo - busy)
    kernels = [o for o in t.ops if "tpu_custom_call" in o.name]
    assert len(kernels) == 7
    assert tr.op_seconds(t, tr.is_pallas) == \
        pytest.approx(sum(o.dur_ns for o in kernels) / 1e9)
    # the SpMM kernel is most of what the device did in this stretch
    assert tr.op_seconds(t, tr.is_pallas) > 0.8 * busy / 1e9
    assert not any(tr.is_pallas(o) for o in t.ops
                   if "X64Combine" in o.name or "ConcatBitcast" in o.name)


def test_merge():
    assert tr.merge([(5, 6), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 6)]


def _ctx():
    t, lo, hi = small_trace()
    shape = {"model": "gcn", "n_layers": 3, "hidden": 256, "classes": 41,
             "feat_dim": 602, "nodes": 1000, "nnz": 20000}
    busy = (tr.busy_ns(t, 0, lo, hi) + tr.busy_ns(t, 1, lo, hi)) / 2 / 1e9
    return types.SimpleNamespace(
        trace=t, busy_s=busy, window_s=1e-6, chips=2,
        counts={"epochs": 4, "rsc_steps": 3, "exact_steps": 1, "evals": 1},
        shape=shape, budget=0.1,
        peak={"flops_per_s": 1.97e14, "bytes_per_s": 8.19e11},
        spans=[("plan", 0.0, 30.0), ("plan", 40.0, 60.0),
               ("eval", 100.0, 300.0)])


def test_metric_readers_by_name():
    ctx = _ctx()
    read = lambda name: spec.metric_reader(name)(ctx)
    assert read("device_idle_frac") == pytest.approx(0.5)
    assert read("spmm_ms") == pytest.approx(800e-9 / 2 * 1e3 / 4)
    assert read("plan_ms.rsc") == pytest.approx(50e-6 / 3)
    assert read("eval_ms") == pytest.approx(200e-6 / 4)
    assert read("spmm_roofline") > 0 and read("step_mfu") > 0


def test_readers_return_nothing_where_nothing_was_read():
    ctx = _ctx()
    ctx.trace = Trace(ops=[o for o in ctx.trace.ops
                           if not tr.is_pallas(o)], host=[])
    ctx.counts = dict(ctx.counts, rsc_steps=0)
    ctx.spans = []
    for name in ("spmm_ms", "spmm_roofline", "plan_ms.rsc", "eval_ms"):
        assert spec.metric_reader(name)(ctx) is None
