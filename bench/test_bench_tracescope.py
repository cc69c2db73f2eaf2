import math
import types
from pathlib import Path

import pytest

import harness
import spec
import tracereduce
import tracescope as ts
from tracereduce import Op, Trace

TESTDATA = Path(__file__).resolve().parent / "testdata"
OLD = TESTDATA / "gcn_rsc_window_60ms.json"
RECORDED = TESTDATA / "gcn_rsc_steps_scoped.json"
KERNEL = '%k = f32[8] custom-call(), custom_call_target="tpu_custom_call"'
EXISTING = ("device_idle_frac", "spmm_ms", "spmm_roofline", "step_mfu",
            "plan_ms.rsc", "eval_ms")
NEW = ("dispatch_ms", "norms_ms.rsc", "refresh_ms.rsc", "spmm_fwd_ms",
       "spmm_sampled_ms.rsc")
SHAPE = {"model": "gcn", "n_layers": 3, "hidden": 256, "classes": 41,
         "feat_dim": 602, "nodes": 23296, "nnz": 2344628}
PEAK = {"flops_per_s": 1.97e14, "bytes_per_s": 8.19e11}


def ctx_of(trace, spans, counts, scoped=None):
    """What the harness's ``read_trace`` hands a reader, from a trace."""
    lo, hi = trace.window(ts.WINDOW)
    trace = trace.clip(lo, hi)
    c = types.SimpleNamespace(
        trace=trace, busy_s=tracereduce.busy_ns(trace, 0, lo, hi) / 1e9,
        window_s=(hi - lo) / 1e9, chips=1, counts=counts, shape=SHAPE,
        budget=0.1, peak=PEAK, spans=spans)
    if scoped is not None:
        c.scoped = ts.clip(scoped, lo, hi)
    return c


def read(name, ctx):
    return spec.metric_reader(name)(ctx)


# -- on small traces made here ----------------------------------------------

def test_innermost_names_each_piece_by_the_shortest_open_span():
    spans = [("step", 0.0, 100.0), ("device_step", 10.0, 90.0),
             ("wait", 40.0, 90.0), ("compile", 15.0, 20.0)]
    assert ts.innermost(spans) == [
        (0.0, 10.0, "step"), (10.0, 15.0, "device_step"),
        (15.0, 20.0, "compile"), (20.0, 40.0, "device_step"),
        (40.0, 90.0, "wait"), (90.0, 100.0, "step")]


def test_idle_by_span_splits_each_gap_and_sums_to_the_idle_time():
    ops = [Op(0, "%fusion.1", 50.0, 150.0), Op(0, KERNEL, 400.0, 300.0),
           Op(1, KERNEL, 0.0, 1000.0)]
    t = Trace(ops=ops, host=[(ts.WINDOW, 0.0, 1000.0)])
    spans = [("step", 150.0, 900.0), ("dispatch", 190.0, 420.0),
             ("compile", 200.0, 300.0)]
    got = ts.idle_by_span(t, 0, 0.0, 1000.0, spans)
    assert got == pytest.approx({"compile": 100e-9, "dispatch": 100e-9,
                                 "step": 200e-9, "outside_spans": 150e-9})
    idle = 1000.0 - tracereduce.busy_ns(t, 0, 0.0, 1000.0)
    assert sum(got.values()) == pytest.approx(idle / 1e9)
    assert ts.named_share(got) == pytest.approx(200 / 550)
    assert ts.idle_by_span(t, 1, 0.0, 1000.0, spans) == {}


@pytest.mark.parametrize("scope,parts", [
    ("jit(rsc_step)/jvp(gcn/spmm0)/spmm_fwd/jit(bcoo_spmm)/bcoo_spmm/"
     "pallas_call", ("rsc_step", "gcn/spmm0", "spmm_fwd")),
    ("jit(rsc_step)/transpose(jvp(sage/spmm2))/spmm_bwd_sampled/"
     "jit(bcoo_spmm)/bcoo_spmm/pallas_call",
     ("rsc_step", "sage/spmm2", "spmm_bwd_sampled")),
    ("jit(exact_step)/transpose(jvp(gcn/spmm1))/spmm_bwd_exact/pallas_call",
     ("exact_step", "gcn/spmm1", "spmm_bwd_exact")),
    ("jit(eval_logits)/gcn/spmm2/spmm_fwd/pallas_call",
     ("eval_logits", "gcn/spmm2", "spmm_fwd")),
    ("", ("?", "?", "?")),
    ("jit(bcoo_spmm)/pallas_call", ("bcoo_spmm", "?", "?")),
])
def test_parse_scope(scope, parts):
    assert ts.parse_scope(scope) == parts


def test_spmm_by_scope_covers_every_pallas_call():
    ops = [ts.ScopedOp(0, KERNEL, 0.0, 5.0,
                       "jit(rsc_step)/jvp(gcn/spmm0)/spmm_fwd/pallas_call"),
           ts.ScopedOp(0, KERNEL, 5.0, 3.0,
                       "jit(eval_logits)/gcn/spmm0/spmm_fwd/pallas_call"),
           ts.ScopedOp(0, KERNEL, 8.0, 2.0, ""),
           ts.ScopedOp(0, "%fusion.3", 10.0, 7.0,
                       "jit(rsc_step)/jvp(gcn/spmm0)/spmm_fwd/add")]
    t = Trace(ops=ops, host=[])
    assert ts.spmm_by_scope(t) == pytest.approx({
        "?:?:?": 2e-9, "eval_logits:gcn/spmm0:spmm_fwd": 3e-9,
        "rsc_step:gcn/spmm0:spmm_fwd": 5e-9})
    assert ts.kernel_seconds(t, "spmm_fwd") == pytest.approx(5e-9)
    assert ts.kernel_seconds(t, "spmm_fwd", ("eval_logits",)) == \
        pytest.approx(3e-9)


def test_new_readers_read_nothing_from_a_program_without_spans_or_scopes():
    """The parent program: no such spans, no scopes on its operations."""
    t = Trace(ops=[ts.ScopedOp(0, KERNEL, 0.0, 5.0)],
              host=[(ts.WINDOW, 0.0, 10.0)])
    ctx = ctx_of(t, [("plan", 1.0, 2.0), ("eval", 3.0, 4.0)],
                 {"epochs": 2, "rsc_steps": 1, "exact_steps": 1,
                  "evals": 1}, scoped=t)
    for name in NEW:
        assert read(name, ctx) is None


def test_no_profile_means_no_scoped_operations(tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "PROFILE_DIR", tmp_path)
    ctx = types.SimpleNamespace(trace=Trace(ops=[], host=[]))
    assert ts.of(ctx) is None


# -- on the existing recorded window: the readers see no change -------------

def test_existing_readers_and_breakdown_read_the_same_through_the_new_loader():
    before = tracereduce.load_json(str(OLD))
    after, spans, _ = ts.load_json(str(OLD))
    assert all(isinstance(o, ts.ScopedOp) and o.scope == "" for o in after.ops)
    spans = [("plan", 1e6, 2e6), ("eval", 9.7e6, 9.9e6),
             ("device_step", 9.9e6, 21e6)]
    counts = {"epochs": 1, "rsc_steps": 1, "exact_steps": 0, "evals": 1}
    old, new = ctx_of(before, spans, counts), ctx_of(after, spans, counts)
    for name in EXISTING:
        assert read(name, new) == read(name, old) is not None
    lo, hi = before.window(ts.WINDOW)
    assert tracereduce.top_ops(new.trace) == tracereduce.top_ops(old.trace)
    assert tracereduce.top_gaps(new.trace, 0, lo, hi, spans) == \
        tracereduce.top_gaps(old.trace, 0, lo, hi, spans)


# -- on the recorded chip steps: spans and scopes ---------------------------

@pytest.fixture(scope="module")
def recorded():
    trace, spans, counts = ts.load_json(str(RECORDED))
    return trace, spans, counts


def _span_ms(spans, name):
    return [(e - s) / 1e6 for n, s, e in spans if n == name]


def _kernel_ms(trace, direction, programs):
    return sum(o.dur_ns for o in trace.ops if "tpu_custom_call" in o.name
               and direction in o.scope.split("/")
               and o.scope.split("/")[0] in programs) / 1e6


@pytest.mark.parametrize("name,expect", [
    ("dispatch_ms", lambda t, sp, n: sum(_span_ms(sp, "dispatch")) / n),
    ("norms_ms.rsc", lambda t, sp, n: sum(_span_ms(sp, "norms")) / n),
    ("refresh_ms.rsc", lambda t, sp, n: _span_ms(sp, "plan.refresh")[0]),
    ("spmm_fwd_ms", lambda t, sp, n: _kernel_ms(
        t, "spmm_fwd", ("jit(rsc_step)", "jit(exact_step)")) / n),
    ("spmm_sampled_ms.rsc", lambda t, sp, n: _kernel_ms(
        t, "spmm_bwd_sampled", ("jit(rsc_step)",)) / n),
])
def test_new_readers_on_recorded_steps(recorded, name, expect):
    trace, spans, counts = recorded
    assert counts["rsc_steps"] == counts["epochs"] == ts.CUT_STEPS
    got = read(name, ctx_of(trace, spans, counts, scoped=trace))
    assert math.isfinite(got) and got > 0
    assert got == pytest.approx(expect(trace, spans, counts["rsc_steps"]))


def test_recorded_steps_idle_by_span_and_spmm_by_scope(recorded):
    trace, spans, _ = recorded
    lo, hi = trace.window(ts.WINDOW)
    by_span = ts.idle_by_span(trace, 0, lo, hi, spans)
    idle = (hi - lo - tracereduce.busy_ns(trace, 0, lo, hi)) / 1e9
    assert sum(by_span.values()) == pytest.approx(idle, rel=1e-9)
    assert {"dispatch", "wait", "norms", "rng", "loss", "plan.build",
            "eval.logits", "eval.metric"} <= set(by_span)
    assert ts.named_share(by_span) >= 0.75
    by_scope = ts.spmm_by_scope(trace)
    assert sum(by_scope.values()) == pytest.approx(
        tracereduce.op_seconds(trace, tracereduce.is_pallas), rel=1e-9)
    assert set(by_scope) == (
        {f"rsc_step:gcn/spmm{l}:{d}" for l in range(3)
         for d in ("spmm_fwd", "spmm_bwd_sampled")}
        | {f"eval_logits:gcn/spmm{l}:spmm_fwd" for l in range(3)})


# -- the profile's own bytes -------------------------------------------------

def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _ld(field, payload):
    payload = payload.encode() if isinstance(payload, str) else payload
    return _varint(field << 3 | 2) + _varint(len(payload)) + payload


def _vi(field, n):
    return _varint(field << 3) + _varint(n)


def _plane(name, stat_names, metas):
    """An XPlane: stat metadata by id, event metadata (id, name, stats)."""
    out = _vi(1, 1) + _ld(2, name) + _ld(3, _ld(2, "XLA Ops"))
    for mid, (op, stats) in metas.items():
        body = _vi(1, mid) + _ld(2, op) + _ld(4, op[:9])
        for sid, value in stats:
            body += _ld(5, _vi(1, sid) + (_ld(5, value) if isinstance(
                value, str) else _vi(3, value)))
        out += _ld(4, _vi(1, mid) + _ld(2, body))
    for sid, sname in stat_names.items():
        out += _ld(5, _vi(1, sid) + _ld(2, _vi(1, sid) + _ld(2, sname)))
    return out


def test_op_scopes_reads_each_device_operations_metadata(tmp_path):
    k = '%bcoo_spmm.6 = f32[8] custom-call(), custom_call_target="x"'
    fwd = "jit(rsc_step)/jvp(gcn/spmm0)/spmm_fwd/bcoo_spmm/pallas_call"
    stats = {3: "tf_op", 4: "program_id", 5: "hlo_category"}
    device = _plane("/device:TPU:0", stats, {
        7: (k, [(5, "custom-call"), (4, 2 ** 63 + 5), (3, fwd + ":")]),
        8: (k, [(4, 11), (3, "jit(eval_logits)/gcn/spmm0/spmm_fwd:")]),
        9: ("%fusion.1", [(4, 11)])})
    host = _plane("/host:CPU", stats, {7: (k, [(4, 1), (3, "not a scope")])})
    path = tmp_path / "x.xplane.pb"
    path.write_bytes(_ld(1, host) + _ld(1, device) + _ld(2, "an error"))
    assert ts.op_scopes(str(path)) == {
        (2 ** 63 + 5, k): fwd, (11, k): "jit(eval_logits)/gcn/spmm0/spmm_fwd"}
    assert ts._program_id("jit_rsc_step(9961609534228586219)") == \
        9961609534228586219


def test_traced_small_cell_on_the_cpu_reads_program_spans(monkeypatch,
                                                         tmp_path):
    """A whole traced run of a small cell: the program's spans come back
    from the profile's host plane, and each new reader reads them or, with
    no device plane to read, nothing."""
    from smallcells import small
    monkeypatch.setattr(harness, "PROFILE_DIR", tmp_path / "profile")
    r = harness.run_cell(small("gcn-reddit-rsc"), seed=2 ** 33 + 17,
                         seconds=1.0, trace=True, require_chip=False)
    assert r["correct"] is True
    for name in ("dispatch_ms", "norms_ms.rsc", "refresh_ms.rsc"):
        assert r["metrics"][name]["value"] > 0
    assert "spmm_fwd_ms" not in r["metrics"]
    trace, spans = ts.load_xplane(
        tracereduce.find_xplane(str(tmp_path / "profile")))
    names = {n for n, _, _ in spans}
    assert {"step", "plan", "plan.refresh", "plan.build", "dispatch",
            "wait", "norms", "eval.logits", "eval.metric"} <= names
    assert trace.window(ts.WINDOW)
