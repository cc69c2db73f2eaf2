"""From a profiler trace to device busy time, kernel time and idle gaps.

The JAX profiler writes ``<dir>/plugins/profile/<run>/*.xplane.pb``.
:func:`load_xplane` keeps the device operations of the chips the run used
(the ``XLA Ops`` line of each ``/device:`` plane, where each op is named
by its HLO text) and the host events of the harness's own annotations. Everything after loading works on plain
tuples, so the tests feed it a small recorded trace (:func:`load_json`).
"""
from __future__ import annotations

import dataclasses
import glob
import json
import os


@dataclasses.dataclass(frozen=True)
class Op:
    device: int
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Trace:
    ops: list            # [Op] device operations
    host: list           # [(name, start_ns, dur_ns)] harness annotations

    def window(self, name: str) -> tuple[float, float]:
        """(start, end) of the host annotation ``name``."""
        for n, s, d in self.host:
            if n == name:
                return s, s + d
        raise LookupError(f"annotation {name!r} not in the trace")

    def clip(self, lo: float, hi: float) -> "Trace":
        """The operations' parts that fall in [lo, hi]."""
        ops = [Op(o.device, o.name, max(o.start_ns, lo),
                  min(o.end_ns, hi) - max(o.start_ns, lo))
               for o in self.ops if o.end_ns > lo and o.start_ns < hi]
        return Trace(ops=ops, host=self.host)


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _device_index(plane_name: str) -> int | None:
    # "/device:TPU:0" -> 0; host planes ("/host:CPU") -> None
    if not plane_name.startswith("/device:"):
        return None
    tail = plane_name.rsplit(":", 1)[-1]
    return int(tail) if tail.isdigit() else None


def load_xplane(path: str, annotations: tuple[str, ...],
                devices: set[int] | None = None) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops, host = [], []
    for plane in data.planes:
        dev = _device_index(plane.name)
        if dev is None:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in annotations:
                        host.append((ev.name, float(ev.start_ns),
                                     float(ev.duration_ns)))
            continue
        if devices is not None and dev not in devices:
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            ops.extend(Op(dev, ev.name, float(ev.start_ns),
                          float(ev.duration_ns)) for ev in line.events)
    return Trace(ops=ops, host=host)


def load_json(path: str) -> Trace:
    raw = json.loads(open(path).read())
    return Trace(ops=[Op(*o) for o in raw["ops"]],
                 host=[tuple(h) for h in raw["host"]])


def merge(intervals) -> list[tuple[float, float]]:
    """Union of ``(start, end)`` intervals, sorted and disjoint."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(trace: Trace, device: int, lo: float, hi: float) -> float:
    """Nanoseconds of [lo, hi] in which some operation ran on ``device``."""
    spans = merge((max(o.start_ns, lo), min(o.end_ns, hi))
                  for o in trace.ops if o.device == device)
    return sum(max(e - s, 0.0) for s, e in spans)


def idle_gaps(trace: Trace, device: int, lo: float, hi: float):
    """The intervals of [lo, hi] in which ``device`` ran nothing."""
    busy = merge((max(o.start_ns, lo), min(o.end_ns, hi))
                 for o in trace.ops if o.device == device)
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def label_gap(gap, spans) -> str:
    """Name of the innermost program span open at the gap's midpoint."""
    mid = (gap[0] + gap[1]) / 2
    best = None
    for name, s, e in spans:
        if s <= mid <= e and (best is None or e - s < best[2] - best[1]):
            best = (name, s, e)
    return best[0] if best else "outside_spans"


def is_pallas(op: Op) -> bool:
    """A Pallas (Mosaic) kernel launch: the TPU trace names each op by its
    HLO text, and a kernel's carries ``custom_call_target="tpu_custom_call"``
    (other custom calls, such as ``X64Combine``, are XLA's own)."""
    return "tpu_custom_call" in op.name


def short_name(name: str, width: int = 96) -> str:
    """An op's HLO text cut to its name and the start of its result type,
    ``%bcoo_spmm.3 = f32[23296,256]...``; programs reuse names."""
    return name if len(name) <= width else name[:width] + "..."


def op_seconds(trace: Trace, pred=None) -> float:
    return sum(o.dur_ns for o in trace.ops
               if pred is None or pred(o)) / 1e9


def top_ops(trace: Trace, n: int = 10) -> list[list]:
    """The ``n`` operations with the most device time, in seconds."""
    tot: dict[str, float] = {}
    for o in trace.ops:
        tot[o.name] = tot.get(o.name, 0.0) + o.dur_ns / 1e9
    return [[short_name(k), v] for k, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def top_gaps(trace: Trace, device: int, lo: float, hi: float, spans,
             n: int = 10) -> list[list]:
    gaps = sorted(idle_gaps(trace, device, lo, hi),
                  key=lambda g: g[0] - g[1])[:n]
    return [[label_gap(g, spans), (g[1] - g[0]) / 1e9] for g in gaps]
