"""Program spans and SpMM scopes from a profiler trace.

``tracereduce.py`` keeps each device operation's HLO text and the
harness's own annotation. While the program's tracer is on, each of its
spans (``repro.obs.trace.SPAN_NAMES``) is also a profiler annotation in
the host plane, on the trace's own clock; and each device operation
carries the name-stack path of the code that issued it in the
:data:`SCOPE_STAT` stat of its ``XLA Ops`` event's metadata, such as
``jit(rsc_step)/transpose(jvp(gcn/spmm0))/spmm_bwd_sampled/jit(bcoo_spmm)/
bcoo_spmm/pallas_call``. This module reads both beside ``tracereduce``:

* :func:`load_xplane` gives a :class:`tracereduce.Trace` whose operations
  carry their ``scope`` (:class:`ScopedOp`), and the host spans: the
  program's, and JAX's own tracing, lowering and compiling under the label
  ``compile``;
* :func:`idle_by_span` names the device's idle time by the innermost host
  span open at each instant; :func:`spmm_by_scope` splits the Pallas
  calls' device time by (program, layer, direction);
* :func:`of` gives a metric reader the window's scoped operations.

Run from the root of a checkout after a traced run of the benchmark, or
on any profile of the program taken with its tracer on:

    python3 bench/tracescope.py [--profile .bench_profile] [--device 0]
        [--cut FIXTURE]

prints the window's ``idle_by_span``, ``spmm_by_scope``, the checks on
them and the longest idle gaps with the spans open over each; ``--cut``
writes :data:`CUT_STEPS` steps of the window as a test fixture.
"""
from __future__ import annotations

import bisect
import dataclasses
import heapq
import json
import os
import re
import sys

import tracereduce
from tracereduce import Op, Trace, is_pallas

WINDOW = "bench.window"
#: The ``XLA Ops`` event stat that holds an operation's name-stack path.
SCOPE_STAT = "tf_op"
COMPILE = "compile"
OUTSIDE = "outside_spans"
#: JAX's own host events for tracing, lowering and compiling a program.
JAX_COMPILE_EVENTS = frozenset({
    "trace_to_jaxpr_dynamic", "trace_to_jaxpr_nounits",
    "lower_sharding_computation", "lower_parallel_callable",
    "backend_compile", "backend_compile_and_load"})
TRAIN_PROGRAMS = ("rsc_step", "exact_step")
#: Steps of a window that ``--cut`` keeps as a test fixture.
CUT_STEPS = 4
DIRECTIONS = ("spmm_fwd", "spmm_bwd_exact", "spmm_bwd_sampled")


@dataclasses.dataclass(frozen=True)
class ScopedOp(Op):
    scope: str = ""


def span_names() -> tuple:
    """The program's span names; none where the program has no such list."""
    try:
        from repro.obs.trace import SPAN_NAMES
    except ImportError:
        return ()
    return tuple(SPAN_NAMES)


def is_compile(name: str) -> bool:
    return (name in JAX_COMPILE_EVENTS or name.endswith("::Compile")
            or name.endswith(".compile"))


# -- the device operations' scopes -------------------------------------------
#
# ``ProfileData`` gives each event's own stats, but ``tf_op`` is a stat of the
# event's metadata, shared by every run of that operation. The metadata is
# read here from the ``.xplane.pb`` itself (the XSpace protocol buffer): each
# device plane's event metadata, keyed by the program it belongs to and the
# operation's HLO text, which is the event's name.

def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, lo: int = 0, hi: int | None = None):
    """``(field number, value)`` of a protocol buffer message: an int for a
    varint, ``(start, end)`` for a length-delimited field."""
    hi = len(buf) if hi is None else hi
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            value, i = bytes(buf[i:i + n]), i + n
        else:
            raise ValueError(f"protocol buffer wire type {wire} at {i}")
        yield key >> 3, value


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def op_scopes(path: str) -> dict[tuple[int, str], str]:
    """``(program id, HLO text) -> name-stack path`` of every device
    operation in a profile, from its metadata's :data:`SCOPE_STAT`."""
    buf = memoryview(open(path, "rb").read())
    out: dict[tuple[int, str], str] = {}
    for num, plane in _fields(buf):            # XSpace.planes
        if num != 1:
            continue
        metas, stat_names = [], {}
        for f, v in _fields(buf, *plane):
            if f == 2 and not _text(buf, v).startswith("/device:"):
                break                           # XPlane.name: a host plane
            if f == 4:                          # XPlane.event_metadata
                metas.extend(v2 for f2, v2 in _fields(buf, *v) if f2 == 2)
            elif f == 5:                        # XPlane.stat_metadata
                for f2, v2 in _fields(buf, *v):
                    if f2 == 2:
                        meta = dict(_fields(buf, *v2))
                        if 2 in meta:
                            stat_names[meta.get(1)] = _text(buf, meta[2])
        ids = {n: k for k, n in stat_names.items()}
        scope_id, prog_id = ids.get(SCOPE_STAT), ids.get("program_id")
        for m in metas:                         # XEventMetadata
            op, scope, prog = "", "", None
            for f, v in _fields(buf, *m):
                if f == 2:
                    op = _text(buf, v)
                elif f == 5:                    # XStat
                    st = dict(_fields(buf, *v))
                    if st.get(1) == scope_id and 5 in st:
                        scope = _text(buf, st[5])
                    elif st.get(1) == prog_id:
                        prog = st.get(3, st.get(4))
            if scope and prog is not None:
                out[(prog, op)] = scope.removesuffix(":")
    return out


def _program_id(module: str) -> int | None:
    # an "XLA Modules" event: "jit_rsc_step(2784065963307818576)"
    m = re.search(r"\((\d+)\)$", module)
    return int(m.group(1)) if m else None


def load_xplane(path: str, devices: set[int] | None = None,
                names: tuple | None = None) -> tuple[Trace, list]:
    """(trace, spans): the device operations with their scopes and the
    harness's annotation, as ``tracereduce.load_xplane`` keeps them; and
    the host spans ``[(name, start_ns, end_ns)]``, the program's by name
    and JAX's compile events as ``compile``."""
    from jax.profiler import ProfileData

    names = set(span_names() if names is None else names)
    scopes = op_scopes(path)
    data = ProfileData.from_file(path)
    ops, host, spans = [], [], []
    for plane in data.planes:
        dev = tracereduce._device_index(plane.name)
        if dev is None:
            for line in plane.lines:
                for ev in line.events:
                    s, d = float(ev.start_ns), float(ev.duration_ns)
                    if ev.name == WINDOW:
                        host.append((ev.name, s, d))
                    elif ev.name in names:
                        spans.append((ev.name, s, s + d))
                    elif is_compile(ev.name):
                        spans.append((COMPILE, s, s + d))
            continue
        if devices is not None and dev not in devices:
            continue
        lines = {line.name: line for line in plane.lines}
        modules = sorted((float(ev.start_ns), _program_id(ev.name))
                         for ev in (lines["XLA Modules"].events
                                    if "XLA Modules" in lines else ()))
        starts = [m[0] for m in modules]
        for ev in (lines["XLA Ops"].events if "XLA Ops" in lines else ()):
            start = float(ev.start_ns)
            k = bisect.bisect_right(starts, start) - 1
            prog = modules[k][1] if k >= 0 else None
            ops.append(ScopedOp(dev, ev.name, start, float(ev.duration_ns),
                                scopes.get((prog, ev.name), "")))
    return Trace(ops=ops, host=host), spans


def load_json(path: str) -> tuple[Trace, list, dict]:
    """(trace, spans, counts) of a fixture: ``ops`` rows of ``[device,
    name, start_ns, dur_ns]`` and an optional scope, ``host`` rows as
    ``tracereduce.load_json`` reads them, optional ``spans`` and
    ``counts``."""
    raw = json.loads(open(path).read())
    trace = Trace(ops=[ScopedOp(*o) for o in raw["ops"]],
                  host=[tuple(h) for h in raw["host"]])
    return (trace, [tuple(s) for s in raw.get("spans", [])],
            raw.get("counts", {}))


def clip(trace: Trace, lo: float, hi: float) -> Trace:
    """``Trace.clip`` that keeps each operation's scope."""
    ops = [dataclasses.replace(o, start_ns=max(o.start_ns, lo),
                               dur_ns=min(o.end_ns, hi) - max(o.start_ns, lo))
           for o in trace.ops if o.end_ns > lo and o.start_ns < hi]
    return Trace(ops=ops, host=trace.host)


# -- idle time by span -------------------------------------------------------

def innermost(spans) -> list[tuple[float, float, str]]:
    """The spans' union cut into pieces ``(start, end, name)``, each named
    by the shortest span open over it (spans of one thread nest)."""
    points = sorted({t for _, s, e in spans for t in (s, e)})
    by_start = sorted(spans, key=lambda x: x[1])
    open_, out, i = [], [], 0
    for a, b in zip(points, points[1:]):
        while i < len(by_start) and by_start[i][1] <= a:
            n, s, e = by_start[i]
            heapq.heappush(open_, (e - s, e, n))
            i += 1
        while open_ and open_[0][1] <= a:
            heapq.heappop(open_)
        if open_:
            out.append((a, b, open_[0][2]))
    return out


def idle_by_span(trace: Trace, device: int, lo: float, hi: float,
                 spans) -> dict[str, float]:
    """Seconds of [lo, hi] in which ``device`` ran nothing, by the
    innermost host span open at the time (``outside_spans`` where none
    was). The values sum to the window's idle time."""
    pieces = innermost([(n, max(s, lo), min(e, hi)) for n, s, e in spans
                        if e > lo and s < hi])
    out: dict[str, float] = {}
    j = 0
    for g0, g1 in tracereduce.idle_gaps(trace, device, lo, hi):
        while j < len(pieces) and pieces[j][1] <= g0:
            j += 1
        t, k = g0, j
        while t < g1:
            if k < len(pieces) and pieces[k][0] < g1:
                a, b, name = pieces[k]
                if a > t:
                    out[OUTSIDE] = out.get(OUTSIDE, 0.0) + (a - t)
                    t = a
                end = min(b, g1)
                out[name] = out.get(name, 0.0) + (end - t)
                t = end
                k += 1
            else:
                out[OUTSIDE] = out.get(OUTSIDE, 0.0) + (g1 - t)
                t = g1
    return {k: v / 1e9 for k, v in sorted(out.items(), key=lambda kv: -kv[1])}


def named_share(by_span: dict[str, float]) -> float:
    """Share of the idle time held by spans other than ``step`` itself and
    ``outside_spans``."""
    total = sum(by_span.values())
    named = sum(v for k, v in by_span.items() if k not in ("step", OUTSIDE))
    return named / total if total > 0 else 0.0


# -- SpMM time by scope ------------------------------------------------------

def parse_scope(scope: str) -> tuple[str, str, str]:
    """(program, layer, direction) of a name-stack path; ``?`` for a part
    the path lacks."""
    m = re.match(r"jit\(([^)]*)\)", scope)
    program = m.group(1) if m else "?"
    m = re.search(r"([A-Za-z0-9_]+/spmm\d+)", scope)
    layer = m.group(1) if m else "?"
    parts = set(re.split(r"[/()]", scope))
    direction = next((d for d in DIRECTIONS if d in parts), "?")
    return program, layer, direction


def spmm_by_scope(trace: Trace) -> dict[str, float]:
    """Device seconds of the Pallas calls by ``program:layer:direction``."""
    out: dict[str, float] = {}
    for o in trace.ops:
        if is_pallas(o):
            key = ":".join(parse_scope(getattr(o, "scope", "")))
            out[key] = out.get(key, 0.0) + o.dur_ns / 1e9
    return dict(sorted(out.items()))


def kernel_seconds(trace: Trace, direction: str,
                   programs: tuple = TRAIN_PROGRAMS) -> float:
    """Device seconds of the Pallas calls in ``direction`` issued by the
    given programs."""
    total = 0.0
    for o in trace.ops:
        if is_pallas(o):
            prog, _, d = parse_scope(getattr(o, "scope", ""))
            if d == direction and prog in programs:
                total += o.dur_ns
    return total / 1e9


# -- for the metric readers --------------------------------------------------

def of(ctx) -> Trace | None:
    """The window's operations with their scopes, read once from the
    harness's profile of the window and kept as ``ctx.scoped`` (where a
    caller set ``ctx.scoped``, that). None where there is no profile."""
    if getattr(ctx, "scoped", None) is None:
        import harness
        try:
            path = tracereduce.find_xplane(str(harness.PROFILE_DIR))
        except FileNotFoundError:
            return None
        lo, hi = ctx.trace.window(WINDOW)
        trace, _ = load_xplane(path, {o.device for o in ctx.trace.ops},
                               names=())
        ctx.scoped = clip(trace, lo, hi)
    return ctx.scoped


# -- command line ------------------------------------------------------------

def report(trace: Trace, spans, device: int, n_gaps: int = 10) -> dict:
    """The window's idle time by span, SpMM time by scope, their checks,
    and the longest idle gaps with every span open over each. The window
    is the harness's annotation, or else what the operations and spans
    cover."""
    try:
        lo, hi = trace.window(WINDOW)
    except LookupError:
        extent = ([(o.start_ns, o.end_ns) for o in trace.ops]
                  + [(s, e) for _, s, e in spans])
        if not extent:
            raise ValueError("the profile holds no device operation and "
                             "no program span") from None
        lo, hi = min(a for a, _ in extent), max(b for _, b in extent)
    trace = clip(trace, lo, hi)
    by_span = idle_by_span(trace, device, lo, hi, spans)
    idle = (hi - lo - tracereduce.busy_ns(trace, device, lo, hi)) / 1e9
    by_scope = spmm_by_scope(trace)
    kernels = tracereduce.op_seconds(trace, is_pallas)
    gaps = sorted(tracereduce.idle_gaps(trace, device, lo, hi),
                  key=lambda g: g[0] - g[1])[:n_gaps]
    longest = []
    for g0, g1 in gaps:
        over = [n for _, n in sorted((s, n) for n, s, e in spans
                                     if s < g1 and e > g0)]
        longest.append({"ms": (g1 - g0) / 1e6,
                        "at_s": (g0 - lo) / 1e9,
                        "open": list(dict.fromkeys(over))})
    return {"window_s": (hi - lo) / 1e9, "idle_s": idle,
            "idle_by_span": by_span,
            "idle_by_span_sum_over_idle": (sum(by_span.values()) / idle
                                           if idle > 0 else None),
            "named_share": named_share(by_span),
            "spmm_by_scope": by_scope,
            "spmm_by_scope_sum_over_kernels": (sum(by_scope.values())
                                               / kernels if kernels else None),
            "compile_events": sum(1 for n, s, e in spans
                                  if n == COMPILE and e > lo and s < hi),
            "longest_gaps": longest}


def cut(trace: Trace, spans, lo: float, hi: float) -> dict:
    """[lo, hi] of a trace as a fixture: Pallas calls keep a short name
    and their scope, other operations their instruction name alone."""
    pallas = '%bcoo_spmm = custom-call(), custom_call_target="tpu_custom_call"'
    ops = []
    for o in clip(trace, lo, hi).ops:
        if is_pallas(o):
            ops.append([o.device, pallas, o.start_ns - lo, o.dur_ns,
                        o.scope])
        else:
            ops.append([o.device, o.name.split(" ")[0], o.start_ns - lo,
                        o.dur_ns])
    kept = [[n, max(s, lo) - lo, min(e, hi) - lo] for n, s, e in spans
            if e > lo and s < hi]
    steps = [(s, e) for n, s, e in kept if n == "step"]
    plans = [s for n, s, e in kept if n == "plan"]
    rsc = sum(1 for s, e in steps if any(s <= p <= e for p in plans))
    return {"host": [[WINDOW, 0.0, hi - lo]], "ops": ops, "spans": kept,
            "counts": {"epochs": len(steps), "rsc_steps": rsc,
                       "exact_steps": len(steps) - rsc,
                       "evals": sum(1 for s in kept if s[0] == "eval")}}


def main(argv) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--profile", default=".bench_profile")
    ap.add_argument("--device", type=int, default=0)
    ap.add_argument("--cut", default=None, metavar="FIXTURE")
    args = ap.parse_args(argv)
    trace, spans = load_xplane(tracereduce.find_xplane(args.profile),
                               devices={args.device})
    rep = report(trace, spans, args.device)
    print(json.dumps(rep, indent=1))
    if args.cut:
        # from one step before a plan refresh inside a job: the window's
        # second (its first comes with the job's first step)
        lo, hi = trace.window(WINDOW)
        refreshes = sorted(s for n, s, e in spans if n == "plan.refresh"
                           and lo < s < hi)
        refresh = refreshes[min(1, len(refreshes) - 1)]
        starts = sorted(s for n, s, e in spans if n == "step" and lo < s < hi)
        i = max(0, sum(1 for s in starts if s < refresh) - 1)
        a = starts[i]
        b = starts[i + CUT_STEPS] if i + CUT_STEPS < len(starts) else hi
        with open(args.cut, "w") as f:
            json.dump({"about": "", **cut(trace, spans, a, b)}, f)
    return 0


if __name__ == "__main__":
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
    sys.exit(main(sys.argv[1:]))
