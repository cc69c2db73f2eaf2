"""One run of one benchmark cell.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A run has four parts.

1. Set-up (``setup_s``, from process start): the graph from the seed
   (``graphgen.py``), the program's full-batch trainer built on it
   (``repro.train.loop.GNNTrainer``: tiling, upload, jitted steps), and a
   warm-up of one whole job, ``epochs`` epochs from the seed's initial
   weights on the program's own schedule. The job compiles every program
   the window runs; its final test accuracy is ``test_acc``.
2. The window (``epoch_s``): the same engine runs the same job again,
   whole jobs on the program's own schedule (RSC for the first
   ``rsc_fraction`` of the epochs, then exact; plans refreshed every
   ``refresh_every`` RSC steps; an evaluation every ``eval_every``
   epochs), until a job ends at or past ``--seconds``. ``epoch_s`` is the
   window's length over the epochs it completed.
3. With ``--trace 1`` the window runs under the JAX profiler and the
   engine's span tracer, and the per-layer metrics (``metrics/*.py``) are
   read from both.
4. The check (``correct``): the warm-up's first three steps, and the three
   from its first plan refresh on, against the plain reference
   (``reference.py``, ``compare.py``), run after the program's state is
   freed.
"""
from __future__ import annotations

import contextlib
import gc
import json
import shutil
import sys
import time
import types

import numpy as np

import compare
import graphgen
import reference
import spec
import tracereduce
import work

CACHE_DIR = spec.ROOT / ".jax_cache"
PROFILE_DIR = spec.ROOT / ".bench_profile"
WINDOW = "bench.window"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def configure_jax() -> None:
    """Persistent compilation cache at a fixed path inside the checkout."""
    import jax
    CACHE_DIR.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def chips_for(n: int, require_chip: bool) -> list:
    import jax
    devs = jax.devices()
    if require_chip and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's devices are {devs}")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} chips, JAX has {len(devs)}")
    return devs[:n]


class CompileCounter:
    """Counts lowerings to XLA (each one is a compile or a cache read)."""

    def __init__(self):
        import jax.monitoring
        self.lowerings = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event.endswith("jaxpr_to_mlir_module_duration"):
            self.lowerings += 1


class JobStopped(Exception):
    pass


class GcClock:
    """Python's garbage collections and the time they held the host: a
    window that reads slow may have lost its time there."""

    def __init__(self):
        self.runs, self.seconds, self.longest = 0, 0.0, 0.0
        self._t = None
        gc.callbacks.append(self._on)

    def _on(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            d = time.perf_counter() - self._t
            self.runs, self.seconds = self.runs + 1, self.seconds + d
            self.longest = max(self.longest, d)

    def reading(self) -> dict:
        """The collections so far; the clock stops."""
        if self._on in gc.callbacks:
            gc.callbacks.remove(self._on)
        return {"gc_runs": self.runs, "gc_ms": self.seconds * 1e3,
                "gc_longest_ms": self.longest * 1e3}


class CheckedSource:
    """Stands in front of the engine's data source.

    At the start of each epoch it notes the time and, where asked, keeps
    the engine's state (the check reads the weights and Adam's moments
    after the first steps) or stops the job (``stop_at``, for
    ``control.py``). Everything else is the program's source.
    """

    def __init__(self, inner, engine, snap_epochs=()):
        self._inner = inner
        self._engine = engine
        self.snap_epochs = set(snap_epochs)
        self.snaps: dict = {}
        self.starts: list[float] = []
        self.stop_at: int | None = None

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def batches(self, epoch: int, skip: int = 0):
        if epoch == self.stop_at:
            raise JobStopped
        self.starts.append(time.perf_counter())
        if epoch in self.snap_epochs:
            self.snaps[epoch] = (self._engine.params,
                                 self._engine.opt_state)
        yield from self._inner.batches(epoch, skip=skip)


def refresh_step(traffic: dict) -> int:
    """The job's first step after a plan refresh: the check compares it
    and the two after it."""
    return traffic["refresh_every"] if traffic["caching"] else 1


def program_seed(seed: int) -> int:
    """The weights' seed: JAX's ``PRNGKey`` keeps 32 bits."""
    return seed % (1 << 30)


def graph_data(graph: dict):
    from repro.graphs.synthetic import GraphData
    from repro.sparse.csr import CSR

    n = graph["nodes"]
    adj = CSR.from_coo(graph["rows"], graph["cols"],
                       np.ones(graph["rows"].shape[0], np.float32), (n, n))
    return GraphData(adj=adj, features=graph["features"],
                     labels=graph["labels"], train_mask=graph["train_mask"],
                     val_mask=graph["val_mask"], test_mask=graph["test_mask"],
                     num_classes=graph["classes"], name="reddit")


def train_config(config: dict, traffic: dict, seed: int):
    from repro.train.loop import TrainConfig
    return TrainConfig(
        model=config["model"], n_layers=config["n_layers"],
        hidden=config["hidden"], dropout=config["dropout"],
        batchnorm=config["batchnorm"], lr=config["lr"],
        epochs=traffic["epochs"], seed=seed, metric="accuracy",
        rsc=traffic["rsc"], budget=traffic["budget"],
        step_frac=traffic["step_frac"],
        refresh_every=traffic["refresh_every"],
        allocate_every=traffic["refresh_every"],
        rsc_fraction=traffic["rsc_fraction"], caching=traffic["caching"],
        switching=traffic["switching"], strategy=traffic["strategy"],
        backend=config["backend"], block=config["block"],
        degree_sort=config["degree_sort"])


def build(cell: spec.Cell, graph: dict, pseed: int, patch_engine=None):
    """The program's full-batch trainer on ``graph``, with the benchmark's
    source in front of its data source; and its initial weights."""
    from repro.train.loop import GNNTrainer

    if cell.traffic["mode"] != "full_batch":
        raise ValueError(f"traffic mode {cell.traffic['mode']!r} is not "
                         "supported")
    trainer = GNNTrainer(train_config(cell.config, cell.traffic, pseed),
                         graph_data(graph))
    engine = trainer.engine
    if patch_engine is not None:
        patch_engine(engine)
    r = refresh_step(cell.traffic)
    src = CheckedSource(engine.source, engine,
                        snap_epochs=(1, compare.UPDATE_STEPS, r, r + 1))
    engine.source = src
    return trainer, engine, src, _host(engine.params)


def checked_steps(engine, src: CheckedSource, p0, r: int) -> dict:
    """The program's side of the check, once the job has run past step
    ``r + 2``: every loss up to there, the gradients of steps 0 and ``r``
    as Adam got them (from its first moment before and after the step),
    and the weights before step 0 and after ``compare.UPDATE_STEPS``."""
    import jax
    b1 = engine.opt.b1
    m = {e: _host(src.snaps[e][1]["m"], np.float64) for e in (1, r, r + 1)}
    grad0 = jax.tree.map(lambda a: a / (1 - b1), m[1])
    grad_r = jax.tree.map(lambda a, b: (a - b1 * b) / (1 - b1),
                          m[r + 1], m[r])
    return {"losses": [float(x) for x in engine.history["loss"][:r + 3]],
            "grads": {0: grad0, r: grad_r},
            "params": {0: p0, compare.UPDATE_STEPS:
                       _host(src.snaps[compare.UPDATE_STEPS][0])}}


def reference_run(cell: spec.Cell, graph: dict, pseed: int, dtype=None):
    import jax.numpy as jnp
    config, traffic = cell.config, cell.traffic
    r = refresh_step(traffic)
    prob = reference.problem(graph, config["model"], config["block"])
    cfg = {"hidden": config["hidden"], "classes": graph["classes"],
           "n_layers": config["n_layers"], "batchnorm": config["batchnorm"],
           "model_args": config.get("model_args", {}),
           "dropout": config["dropout"],
           "lr": config["lr"], "rsc": traffic["rsc"],
           "budget": traffic["budget"], "step_frac": traffic["step_frac"],
           "refresh_every": r,
           "rsc_steps": (int(traffic["epochs"] * traffic["rsc_fraction"])
                         if traffic["switching"] else traffic["epochs"])}
    return reference.run(prob, cfg, pseed, r + 3, grad_steps=(0, r),
                         param_steps=(0, compare.UPDATE_STEPS),
                         dtype=dtype or jnp.float32)


def _host(tree, dtype=np.float32):
    import jax
    return jax.tree.map(lambda a: np.asarray(a, dtype), tree)


def _memory_peak(devs) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devs]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def _device_info(devs) -> dict:
    d = devs[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool, *,
             require_chip: bool = True, patch_engine=None,
             t_process: float | None = None) -> dict:
    """Run the cell once; return the result line as a dict."""
    t0 = t_process if t_process is not None else time.perf_counter()
    import jax
    configure_jax()
    devs = chips_for(cell.chips, require_chip)
    device = _device_info(devs)
    peak = work.peaks_for(device["kind"]) if require_chip else None
    print(json.dumps({"device": device}), flush=True)

    from repro import obs

    config, traffic = cell.config, cell.traffic
    pseed = program_seed(seed)
    graph = graphgen.generate(config, seed)
    trainer, engine, src, p0 = build(cell, graph, pseed, patch_engine)
    counter = CompileCounter()

    # -- set-up: one whole job from the seed's weights ---------------------
    job = {"epochs": traffic["epochs"], "eval_every": traffic["eval_every"]}
    engine.train(**job)
    hist = engine.history
    side = checked_steps(engine, src, p0, refresh_step(traffic))
    test_acc = float(hist["test"][-1][1])
    src.snap_epochs, src.snaps = set(), {}
    n_steps0, n_evals0 = len(hist["loss"]), len(hist["test"])
    lowered0 = counter.lowerings
    setup_s = time.perf_counter() - t0

    # -- the window --------------------------------------------------------
    tracer = obs.get_tracer()
    if trace:
        shutil.rmtree(PROFILE_DIR, ignore_errors=True)
        obs.configure(trace=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(PROFILE_DIR), profiler_options=opts)
        t_origin = time.perf_counter()
        tracer.reset()
    mark = (jax.profiler.TraceAnnotation(WINDOW) if trace
            else contextlib.nullcontext())
    t_mark = time.perf_counter()
    with mark:
        gc_clock = GcClock()
        t_w = time.perf_counter()
        src.starts = []
        while True:
            engine.train(**job)
            if time.perf_counter() - t_w >= seconds:
                break
        t_end = time.perf_counter()
    if trace:
        jax.profiler.stop_trace()
        obs.configure(trace=False)
        spans = [(e["name"], e["ts_us"] * 1e3, (e["ts_us"] + e["dur_us"])
                  * 1e3) for e in tracer.snapshot() if e["kind"] == "span"]
    window_s = t_end - t_w
    epochs = len(src.starts)
    modes = hist["mode"][n_steps0:]
    losses = np.asarray(hist["loss"][n_steps0:], np.float64)
    counts = {"epochs": epochs, "rsc_steps": modes.count("rsc"),
              "exact_steps": modes.count("exact"),
              "evals": len(hist["test"]) - n_evals0}
    window = {"seconds": window_s, **counts,
              "compiles": counter.lowerings - lowered0,
              **epoch_times(src.starts, t_end, traffic["epochs"]),
              **gc_clock.reading()}
    print(json.dumps({"window": window}), flush=True)
    device["memory_peak_bytes"] = _memory_peak(devs)

    # -- free the program's state before the reference runs ---------------
    del trainer, engine, src, hist
    gc.collect()

    ref = reference_run(cell, graph, pseed)
    numbers = compare.gaps(side, ref, refresh_step(traffic))
    print(json.dumps({"readings": numbers}), flush=True)
    ok, checks = compare.verdict(numbers, cell.limits)
    failed = int(np.sum(~np.isfinite(losses)))
    attempted = int(losses.size)

    metrics = {}
    result = {"correct": bool(ok and failed == 0 and epochs > 0),
              "attempted": attempted, "failed": failed}
    if trace:
        per_layer, extra, breakdown = read_trace(
            cell, devs, graph, counts, peak, spans, t_origin, t_mark)
        device.update(extra)
        metrics.update(per_layer)
    else:
        values = {"epoch_s": window_s / max(epochs, 1), "test_acc": test_acc,
                  "setup_s": setup_s}
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    result.update(metrics=metrics, device=device)
    if trace:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def epoch_times(starts: list, end: float, job_epochs: int) -> dict:
    """Each job's seconds, the median epoch and the slowest five epochs
    (index in its job, ms): where a slow window lost its time."""
    t = np.asarray(starts + [end])
    ms = np.diff(t) * 1e3
    jobs = t[::job_epochs]
    slow = np.argsort(-ms, kind="stable")[:5]
    return {"job_s": [float(b - a) for a, b in zip(jobs[:-1], jobs[1:])],
            "epoch_ms_median": float(np.median(ms)),
            "slowest_epochs_ms": [[int(i % job_epochs), float(ms[i])]
                                  for i in slow]}


def read_trace(cell, devs, graph, counts, peak, spans, t_origin, t_mark):
    """Per-layer metrics, the device's busy time and the breakdown."""
    ids = {d.id for d in devs}
    tr = tracereduce.load_xplane(tracereduce.find_xplane(str(PROFILE_DIR)),
                                 (WINDOW,), devices=ids)
    lo, hi = tr.window(WINDOW)
    # program spans are perf_counter times from t_origin; the annotation
    # opened at t_mark on the host and at ``lo`` on the trace's clock
    shift = lo - (t_mark - t_origin) * 1e9
    spans = [(n, s + shift, e + shift) for n, s, e in spans
             if e + shift > lo and s + shift < hi]
    tr = tr.clip(lo, hi)
    window_s = (hi - lo) / 1e9
    busy_s = float(np.mean([tracereduce.busy_ns(tr, i, lo, hi)
                            for i in sorted(ids)])) / 1e9
    ctx = types.SimpleNamespace(
        trace=tr, busy_s=busy_s, window_s=window_s, chips=len(devs),
        counts=counts, shape=work.shape_of(cell.config, graph),
        budget=cell.traffic["budget"] if cell.traffic["rsc"] else 1.0,
        peak=peak, spans=spans)
    metrics = {}
    for m in cell.per_layer:
        v = spec.metric_reader(m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    breakdown = {
        "device_ops": tracereduce.top_ops(tr),
        "idle_gaps": tracereduce.top_gaps(tr, min(ids), lo, hi, spans)}
    return metrics, {"busy_s": busy_s, "window_s": window_s}, breakdown


def report(result: dict) -> None:
    """The numbers compared, as the last lines on stderr, then the result
    as the last line on stdout."""
    for name, c in result["checks"].items():
        print(f"{name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


def main(argv, t_process: float | None = None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    try:
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          t_process=t_process)
    except (NoChip, work.UnknownDevice) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    report(result)
    return 0
