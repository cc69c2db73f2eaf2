"""The program's spans in a profiler trace, the engine's span tree, and the
SpMM calls' trace-time names.

* While the tracer is on, every span is also a profiler annotation: the
  spans land in the ``.xplane.pb`` host plane with their names and
  nesting, and a disabled tracer writes none.
* The engine names each piece of host work in its step loop, under the
  parent it belongs to.
* Every SpMM call's operations carry their layer (``gcn/spmm0``) and
  direction (``spmm_fwd``, ``spmm_bwd_exact``, ``spmm_bwd_sampled``) in
  their locations, forward and backward, in the RSC and exact steps.
"""
import glob
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro import obs
from repro.graphs.synthetic import sbm_graph
from repro.obs.context import new_trace
from repro.obs.trace import SPAN_NAMES, Tracer
from repro.train.loop import GNNTrainer, TrainConfig

LAYERS = 3


def _host_events(log_dir, prefix):
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            out.extend((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                       for ev in line.events if ev.name.startswith(prefix))
    return sorted(out, key=lambda e: e[1])


@pytest.mark.parametrize("enabled", [True, False])
@pytest.mark.parametrize("opener", ["span", "span_in"])
def test_spans_reach_the_profiler_host_plane(tmp_path, enabled, opener):
    tracer = Tracer(enabled=enabled)
    if opener == "span":
        open_span = tracer.span
    else:
        ctx = new_trace()
        open_span = lambda name: tracer.span_in(ctx, name)  # noqa: E731
    jax.profiler.start_trace(str(tmp_path))
    try:
        with open_span("t.outer"):
            with open_span("t.inner"):
                jnp.ones(4).block_until_ready()
            with open_span("t.sibling"):
                pass
        tracer.span_at(None, "t.retro", 0.0, 1.0)
    finally:
        jax.profiler.stop_trace()
    events = _host_events(str(tmp_path), "t.")
    if not enabled:
        assert events == []
        return
    # span_at is retroactive: in memory only
    assert [e[0] for e in events] == ["t.outer", "t.inner", "t.sibling"]
    (_, o0, o1), (_, i0, i1), (_, s0, s1) = events
    assert o0 <= i0 < i1 <= s0 < s1 <= o1
    memory = {e["name"]: e for e in tracer.snapshot()}
    assert memory["t.inner"]["parent"] == memory["t.sibling"]["parent"] \
        == "t.outer"
    assert set(memory) == {"t.outer", "t.inner", "t.sibling", "t.retro"}


# ------------------------------------------------------- engine span tree

EPOCHS, REFRESH, EVAL_EVERY = 10, 3, 5
RSC_STEPS = int(EPOCHS * 0.8)


@pytest.fixture(scope="module")
def engine_spans():
    obs.reset(trace=True)
    try:
        graph = sbm_graph(n_nodes=300, n_clusters=5, avg_degree=8,
                          feat_dim=16, seed=0)
        GNNTrainer(TrainConfig(model="gcn", n_layers=2, hidden=16,
                               epochs=EPOCHS, block=32, rsc=True,
                               budget=0.3, refresh_every=REFRESH,
                               allocate_every=REFRESH), graph
                   ).train(eval_every=EVAL_EVERY)
        return [e for e in obs.get_tracer().snapshot()
                if e["kind"] == "span"]
    finally:
        obs.reset()


@pytest.mark.parametrize("name,parent,count", [
    ("rng", None, EPOCHS),
    ("step", None, EPOCHS),
    ("plan", "step", RSC_STEPS),
    ("plan.refresh", "plan", len(range(REFRESH, RSC_STEPS, REFRESH))),
    ("plan.build", "plan.refresh", len(range(REFRESH, RSC_STEPS, REFRESH))),
    ("device_step", "step", EPOCHS),
    ("dispatch", "device_step", EPOCHS),
    ("wait", "device_step", EPOCHS),
    ("norms", "step", RSC_STEPS),
    ("loss", None, EPOCHS),
    ("epoch_end", None, EPOCHS),
    ("eval", None, len(range(0, EPOCHS, EVAL_EVERY)) + 1),
    ("eval.logits", "eval", len(range(0, EPOCHS, EVAL_EVERY)) + 1),
    ("eval.metric", "eval", len(range(0, EPOCHS, EVAL_EVERY)) + 1),
])
def test_engine_span_tree(engine_spans, name, parent, count):
    assert name in SPAN_NAMES
    found = [e for e in engine_spans if e["name"] == name]
    assert len(found) == count
    assert {e["parent"] for e in found} == {parent}


def test_engine_opens_only_listed_spans(engine_spans):
    """The benchmark finds the program's spans in a profile by
    ``SPAN_NAMES``: a span missing there would drop out of the trace."""
    assert {e["name"] for e in engine_spans} <= set(SPAN_NAMES)


def _inside(child, parent):
    return (parent["ts_us"] <= child["ts_us"] and child["ts_us"]
            + child["dur_us"] <= parent["ts_us"] + parent["dur_us"])


def test_plan_and_eval_hold_what_they_held(engine_spans):
    """``plan`` is the RSC step's plan lookup, a refresh inside it on a
    refresh step and nothing of the device step; ``eval`` holds the
    forward, the copy and the metric; the norms stay outside ``plan``."""
    by = lambda n: [e for e in engine_spans if e["name"] == n]  # noqa: E731
    plans, refreshes = by("plan"), by("plan.refresh")
    with_refresh = [i for i, p in enumerate(plans)
                    if any(_inside(r, p) for r in refreshes)]
    assert with_refresh == list(range(REFRESH, RSC_STEPS, REFRESH))
    for p in plans:
        assert not any(_inside(d, p) for d in by("device_step") + by("norms"))
    for ev in by("eval"):
        kids = [k for k in by("eval.logits") + by("eval.metric")
                if _inside(k, ev)]
        assert len(kids) == 2
        assert sum(k["dur_us"] for k in kids) <= ev["dur_us"]
    assert all("dur_ms" not in s["args"] for s in by("step"))


# ------------------------------------------------- SpMM trace-time names

@pytest.fixture(scope="module")
def lowered():
    graph = sbm_graph(n_nodes=300, n_clusters=5, avg_degree=8, feat_dim=16,
                      seed=0)
    engine = GNNTrainer(TrainConfig(model="gcn", n_layers=LAYERS, hidden=16,
                                    epochs=2, block=32, rsc=True),
                        graph).engine
    runner, ops = engine.runner, engine.source.ops
    key = jax.random.PRNGKey(0)
    plans = engine.planner.plans_for(None, 0, engine.schedule)
    texts = {
        "rsc": runner._rsc.lower(engine.params, engine.opt_state, ops,
                                 plans, key).as_text(debug_info=True),
        "exact": runner._exact.lower(engine.params, engine.opt_state, ops,
                                     key).as_text(debug_info=True)}
    return {k: set(re.findall(r'loc\("([^"]*)"', t))
            for k, t in texts.items()}


@pytest.mark.parametrize("step,direction", [
    ("rsc", "spmm_fwd"), ("rsc", "spmm_bwd_sampled"),
    ("exact", "spmm_fwd"), ("exact", "spmm_bwd_exact")])
@pytest.mark.parametrize("layer", range(LAYERS))
def test_spmm_ops_carry_layer_and_direction(lowered, step, direction, layer):
    locs = [loc for loc in lowered[step]
            if f"gcn/spmm{layer}" in loc
            and direction in re.split(r"[/()]", loc)]
    assert locs and all(loc.startswith(f"jit({step}_step)/")
                        for loc in locs)
    # every GCN layer has a plan in the RSC step, none in the exact one
    wrong = {"rsc": "spmm_bwd_exact", "exact": "spmm_bwd_sampled"}[step]
    assert not any(wrong in loc for loc in lowered[step])
