"""Per-signature SpMM tile autotuner with a persisted JSON config cache.

Qiu et al. (*Optimizing Sparse Matrix Multiplications for GNNs*) show the
best SpMM tile shape is input-dependent; our CPU sweeps agree (the winning
streaming ``chunk`` flips between 16 and 128 across operand shapes). This
module owns that decision:

* an operand **signature** buckets the dispatch-relevant statics —
  ``(backend, bm, bk, d, s_pad, n_row_blocks)`` rounded to powers of two
  plus a **density band** (``s_pad / (n_row_blocks · n_col_blocks)``
  quantized to coarse bands) — so one sweep serves every operand in the
  bucket (in particular: every subgraph of a minibatch shape bucket);
* :func:`get_or_tune` sweeps the backend's tunables on synthetic operands
  of the bucket's representative shape — ``chunk`` (tiles per scan step of
  the streaming jnp fallback) and ``bd`` (dense column tile of the
  row-segmented Pallas kernel) — and caches the winner;
* :func:`get_or_tune_auto` goes one level up: it sweeps the SAME
  representative shape across **lowerings** (``stream`` chunked scan,
  ``dense`` scatter-into-dense matmul, ``pallas`` row-segmented kernel on
  real TPU) and records the winning *backend* in the cache alongside its
  tile knobs — the format/knob choice is input-dependent (Qiu et al.),
  and "Fast Training of Sparse GNNs on Dense Hardware" shows the dense
  lowering flips the winner at moderate densities, so the decision is
  per density-band signature, never global;
* :func:`lookup` is the zero-cost trace-time read consulted by
  ``kernels.ops`` / ``core.rsc_spmm`` at dispatch: cached winner if the
  signature was ever tuned (this process or a previous one, via the JSON
  file), heuristic default otherwise. ``lookup`` NEVER sweeps, so cold
  dispatch never stalls a jit trace — but a miss is no longer silent:
  it bumps the ``autotune.miss{sig}`` counter and logs once per
  signature, so cold-cache dispatch is visible in the metrics snapshot.

Cache file format (``RSC_AUTOTUNE_CACHE`` env var, default
``~/.cache/repro-rsc/spmm_autotune.json``)::

    {"version": 1,
     "entries": {"<signature>": {"bd": 512, "chunk": 16, "us": 1234.5,
                                 "backend": "dense",
                                 "platform": "cpu", "device": "...",
                                 "interpret": false}}}

``us`` records the winning candidate's measured microseconds per call and
``backend``/``platform``/``device``/``interpret`` where that timing came
from; for ``auto|...`` signatures ``backend`` is additionally the
DISPATCH DECISION (``stream`` | ``dense`` | ``pallas``) that
``core.rsc_spmm.spmm_apply(backend="auto")`` serves per signature.
Interpret-mode sweeps are provenance, not signal, and dispatch WARNS (and
counts, via ``repro.obs``) when it serves an interpret-timed winner to a
real hardware backend. Unknown keys are preserved on rewrite; writes are
atomic (tmp file + rename).
"""
from __future__ import annotations

import dataclasses
import json
import logging
import os
import time
import uuid
import warnings
from pathlib import Path

import numpy as np

from repro import obs

logger = logging.getLogger(__name__)

# TPU lane width. The Pallas kernel runs at the feature width padded up to
# a multiple of it, and its column tile ``bd`` is a multiple of it.
LANE = 128
CHUNK_CANDIDATES = (8, 16, 32, 64, 128)
BD_CANDIDATES = (128, 256, 512)
DEFAULT_CHUNK = 32
DEFAULT_BD = 512
# Sweep-time caps: candidates are timed at the bucket's representative
# shape clipped to these, keeping any single sweep sub-second-ish on CPU
# while preserving the relative ordering of tile configs. SWEEP_MAX_D
# clips only the streaming and dense sweeps; the Pallas sweep runs at the
# padded width so its ``bd`` candidates divide it.
SWEEP_MAX_S = 1024
SWEEP_MAX_BLOCKS = 64
SWEEP_MAX_D = 512


AUTO_BACKENDS_CPU = ("stream", "dense")


def canonical_backend(name: str) -> str:
    """Canonical backend names are ``stream`` | ``pallas`` | ``dense``.

    ``jnp`` is the legacy alias of the streaming scan;
    ``pallas_interpret`` is the interpret-mode flavor of ``pallas``.
    """
    return {"jnp": "stream", "pallas_interpret": "pallas"}.get(name, name)


@dataclasses.dataclass(frozen=True)
class SpmmConfig:
    bd: int       # dense column tile of the Pallas kernel
    chunk: int    # tiles per scan step of the streaming jnp fallback
    source: str = "default"   # "default" | "swept" | "cache"
    backend: str = "stream"   # chosen lowering: stream | pallas | dense


@dataclasses.dataclass
class TuneStats:
    lookups: int = 0
    hits: int = 0        # lookups/get_or_tune served from the cache
    defaults: int = 0    # lookups answered with the heuristic default
    sweeps: int = 0      # actual timing sweeps run
    interpret_served: int = 0   # interpret-swept entries served to a
                                # real hardware backend (suspect signal)


def _current_platform() -> str:
    """Platform of the default jax device (lazy — import cost only when a
    provenance check actually needs it)."""
    import jax
    return jax.devices()[0].platform


def _current_device_kind() -> str:
    import jax
    return getattr(jax.devices()[0], "device_kind", "unknown")


def _pow2_ceil(x: int) -> int:
    return 1 << max(0, (int(x) - 1).bit_length())


def padded_width(d: int) -> int:
    """Feature width the SpMM kernel runs at: ``d`` rounded up to LANE."""
    return -(-int(d) // LANE) * LANE


def _density_band(s_pad: int, n_row_blocks: int, n_col_blocks: int) -> str:
    dens = s_pad / max(1, n_row_blocks * n_col_blocks)
    for edge in (0.02, 0.05, 0.1, 0.25, 0.5, 1.0):
        if dens <= edge:
            return f"{edge:g}"
    return "inf"


def signature(backend: str, *, bm: int, bk: int, d: int, s_pad: int,
              n_row_blocks: int, n_col_blocks: int) -> str:
    """Bucket an operand's dispatch statics into a cache key.

    ``d`` keys by :func:`padded_width`, so every width that shares a
    signature runs the kernel at the same padded width and a tuned ``bd``
    divides it.
    """
    return (f"{backend}|bm{bm}|bk{bk}|d{padded_width(d)}"
            f"|s{_pow2_ceil(s_pad)}"
            f"|rb{_pow2_ceil(n_row_blocks)}"
            f"|dens{_density_band(s_pad, n_row_blocks, n_col_blocks)}")


class AutotuneCache:
    """In-memory signature→config map, persisted to a JSON file."""

    def __init__(self, path: str | os.PathLike | None = None):
        if path is None:
            path = os.environ.get(
                "RSC_AUTOTUNE_CACHE",
                str(Path.home() / ".cache" / "repro-rsc"
                    / "spmm_autotune.json"))
        self.path = Path(path)
        self.entries: dict[str, dict] = {}
        self.stats = TuneStats()
        self._loaded = False
        self._warned: set[str] = set()   # interpret-served warn-once keys
        self._missed: set[str] = set()   # lookup-miss log-once keys

    def _load(self) -> None:
        if self._loaded:
            return
        self._loaded = True
        try:
            raw = json.loads(self.path.read_text())
            if isinstance(raw, dict) and isinstance(raw.get("entries"), dict):
                self.entries.update(raw["entries"])
        except (OSError, ValueError):
            pass

    def save(self) -> None:
        """Atomic, concurrency-safe persist.

        Concurrent benchmark/CI processes share one cache file, so (a) the
        current file is re-read and MERGED first, a best-effort courtesy to
        concurrent writers (ours win on conflict; a writer publishing
        between our read and our replace can still lose entries — a lost
        sweep result just re-sweeps later, so no lock is worth the cost);
        (b) the temp file name is unique per writer (two writers can never
        interleave bytes in one temp file); (c) the publish is
        ``os.replace`` — readers see the old or the new complete file,
        never a torn one. Corruption is impossible; loss is bounded.
        """
        try:
            self.path.parent.mkdir(parents=True, exist_ok=True)
            try:
                raw = json.loads(self.path.read_text())
                if isinstance(raw, dict) and isinstance(raw.get("entries"),
                                                        dict):
                    merged = dict(raw["entries"])
                    merged.update(self.entries)
                    self.entries = merged
            except (OSError, ValueError):
                pass
            # unique per WRITE, not just per process: concurrent threads
            # of one process must never share a temp file either
            tmp = self.path.with_name(
                f".{self.path.name}.{os.getpid()}.{uuid.uuid4().hex}.tmp")
            try:
                tmp.write_text(json.dumps(
                    {"version": 1, "entries": self.entries},
                    indent=1, sort_keys=True))
                os.replace(tmp, self.path)
            except BaseException:
                tmp.unlink(missing_ok=True)
                raise
            # writers killed between write and replace leave orphans with
            # unique names — sweep OLD siblings so they never accumulate
            # (age-gated: a live concurrent writer's tmp must survive)
            cutoff = time.time() - 3600
            for stale in self.path.parent.glob(f".{self.path.name}.*.tmp"):
                try:
                    if stale.stat().st_mtime < cutoff:
                        stale.unlink()
                except OSError:
                    pass
        except OSError:
            pass  # read-only FS: stay in-memory only

    def get(self, sig: str) -> SpmmConfig | None:
        self._load()
        e = self.entries.get(sig)
        if e is None:
            return None
        # Provenance check: a REAL-pallas dispatch ("pallas|..." signature
        # only exists on actual TPU hardware) being served a winner whose
        # sweep ran in interpret mode. The config is still usable but its
        # timing told us nothing about hardware — warn once per signature
        # and count it, so benchmark provenance stays honest.
        if e.get("interpret") and sig.split("|", 1)[0] == "pallas":
            self.stats.interpret_served += 1
            obs.get_registry().counter("autotune.interpret_served")
            if sig not in self._warned:
                self._warned.add(sig)
                warnings.warn(
                    f"autotune cache entry for {sig!r} was swept in "
                    f"interpret mode (on {e.get('platform', '?')}); its "
                    "timing is not hardware signal — re-sweep on this "
                    "backend (delete the entry or point RSC_AUTOTUNE_CACHE "
                    "at a fresh file)", RuntimeWarning, stacklevel=3)
        backend = canonical_backend(
            str(e.get("backend") or sig.split("|", 1)[0]))
        if backend == "auto":   # pre-backend entry under an auto signature
            backend = "stream"
        return SpmmConfig(bd=int(e.get("bd", DEFAULT_BD)),
                          chunk=int(e.get("chunk", DEFAULT_CHUNK)),
                          source="cache", backend=backend)

    def put(self, sig: str, cfg: SpmmConfig, us: float,
            persist: bool = True,
            provenance: dict | None = None) -> None:
        self._load()
        entry = {"bd": cfg.bd, "chunk": cfg.chunk, "us": round(us, 2)}
        if provenance:
            entry.update(provenance)
        self.entries[sig] = entry
        if persist:
            self.save()


_cache = AutotuneCache()


def get_cache() -> AutotuneCache:
    return _cache


def reset(path: str | os.PathLike | None = None) -> AutotuneCache:
    """Swap the process-wide cache (tests / benchmarks point it at a
    scratch file)."""
    global _cache
    _cache = AutotuneCache(path)
    return _cache


def default_config(d: int) -> SpmmConfig:
    bd = min(DEFAULT_BD, d)
    if d % bd:
        bd = d
    return SpmmConfig(bd=bd, chunk=DEFAULT_CHUNK, source="default")


def lookup(sig: str, d: int | None = None) -> SpmmConfig:
    """Trace-time config read: cached winner or heuristic default.

    Never sweeps — jit traces must not stall on a timing run. A miss is
    still answered instantly (heuristic default) but is no longer
    invisible: it bumps ``autotune.miss{sig}`` and logs once per
    signature, so a cold cache shows up in the metrics snapshot rather
    than only in mysteriously-slow steps.
    """
    _cache.stats.lookups += 1
    cfg = _cache.get(sig)
    if cfg is not None:
        _cache.stats.hits += 1
        return cfg
    _cache.stats.defaults += 1
    obs.get_registry().counter("autotune.miss", sig=sig)
    if sig not in _cache._missed:
        _cache._missed.add(sig)
        logger.info(
            "autotune cache miss for signature %s — dispatching the "
            "heuristic default (run get_or_tune/get_or_tune_auto or point "
            "RSC_AUTOTUNE_CACHE at a warmed cache to remove this)", sig)
    return default_config(d if d is not None else DEFAULT_BD)


def served_bd(sig: str, d: int) -> int:
    """The column tile :func:`lookup` serves for ``sig``, read without
    touching its statistics, counters or log (host-side reporting)."""
    _cache._load()
    e = _cache.entries.get(sig)
    if e is None:
        return default_config(d).bd
    return int(e.get("bd", DEFAULT_BD))


def _bench(fn, iters: int = 3) -> float:
    import jax
    jax.block_until_ready(fn())          # compile + warm
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def get_or_tune(backend: str, *, bm: int, bk: int, d: int, s_pad: int,
                n_row_blocks: int, n_col_blocks: int,
                persist: bool = True) -> SpmmConfig:
    """Cached config for this signature, sweeping once on a miss.

    The second query for the same ``(bucket shape, density band)``
    signature — from any operand in the bucket, or any later process via
    the JSON file — returns the cached winner without re-sweeping.
    """
    sig = signature(backend, bm=bm, bk=bk, d=d, s_pad=s_pad,
                    n_row_blocks=n_row_blocks, n_col_blocks=n_col_blocks)
    cfg = _cache.get(sig)
    if cfg is not None:
        _cache.stats.hits += 1
        return cfg
    cfg, us, prov = _sweep(backend, bm=bm, bk=bk, d=d, s_pad=s_pad,
                           n_row_blocks=n_row_blocks,
                           n_col_blocks=n_col_blocks)
    _cache.stats.sweeps += 1
    _cache.put(sig, cfg, us, persist=persist, provenance=prov)
    reg = obs.get_registry()
    reg.counter("autotune.sweeps", backend=backend)
    reg.observe("autotune.sweep_us", us, backend=backend)
    obs.get_tracer().instant("autotune_sweep", sig=sig, us=round(us, 1),
                             interpret=prov["interpret"])
    return cfg


def auto_backends() -> tuple[str, ...]:
    """Lowering candidates for the cross-backend sweep on this host.

    ``pallas`` joins only on real TPU: interpret-mode timings are pure
    emulation overhead and would poison the ranking (they are provenance,
    never signal — see the interpret-served warning in :meth:`get`).
    """
    from repro.kernels import ops as kops
    if kops.on_tpu():
        return AUTO_BACKENDS_CPU + ("pallas",)
    return AUTO_BACKENDS_CPU


def get_or_tune_auto(*, bm: int, bk: int, d: int, s_pad: int,
                     n_row_blocks: int, n_col_blocks: int,
                     persist: bool = True,
                     backends: tuple[str, ...] | None = None) -> SpmmConfig:
    """Cross-backend winner for this signature, sweeping once on a miss.

    Sweeps every candidate lowering (:func:`auto_backends` unless
    ``backends`` overrides) at the bucket's representative shape, caches
    the fastest as an ``auto|...`` entry whose ``backend`` field is the
    dispatch decision ``core.rsc_spmm.spmm_apply(backend="auto")`` serves.
    Per-backend signatures tuned by :func:`get_or_tune` are untouched —
    the two namespaces coexist in one cache file.
    """
    sig = signature("auto", bm=bm, bk=bk, d=d, s_pad=s_pad,
                    n_row_blocks=n_row_blocks, n_col_blocks=n_col_blocks)
    cfg = _cache.get(sig)
    if cfg is not None:
        _cache.stats.hits += 1
        obs.get_ledger().note_backend(sig, cfg.backend)
        return cfg
    reg = obs.get_registry()
    best: tuple[float, SpmmConfig, dict] | None = None
    for backend in (backends if backends is not None else auto_backends()):
        cand, us, prov = _sweep(backend, bm=bm, bk=bk, d=d, s_pad=s_pad,
                                n_row_blocks=n_row_blocks,
                                n_col_blocks=n_col_blocks)
        _cache.stats.sweeps += 1
        reg.counter("autotune.sweeps", backend=backend)
        reg.observe("autotune.sweep_us", us, backend=backend)
        if best is None or us < best[0]:
            best = (us, cand, prov)
    us, cfg, prov = best
    _cache.put(sig, cfg, us, persist=persist,
               provenance={**prov, "backend": cfg.backend})
    obs.get_tracer().instant("autotune_auto", sig=sig, us=round(us, 1),
                             backend=cfg.backend)
    obs.get_ledger().note_backend(sig, cfg.backend)
    return cfg


def _sweep(backend: str, *, bm: int, bk: int, d: int, s_pad: int,
           n_row_blocks: int, n_col_blocks: int,
           ) -> tuple[SpmmConfig, float, dict]:
    """Time each candidate on synthetic operands of the bucket shape."""
    import jax.numpy as jnp

    from repro.core.rsc_spmm import spmm_stream

    # Representative (clipped) shapes — candidates keep their relative
    # ordering; absolute times are only provenance.
    s_rep = min(_pow2_ceil(s_pad), SWEEP_MAX_S)
    rb_rep = min(_pow2_ceil(n_row_blocks), SWEEP_MAX_BLOCKS)
    cb_rep = min(_pow2_ceil(n_col_blocks), SWEEP_MAX_BLOCKS)
    d_rep = d if d <= SWEEP_MAX_D else SWEEP_MAX_D

    rng = np.random.default_rng(0)
    blocks = jnp.asarray(
        np.concatenate([rng.standard_normal((s_rep, bm, bk)),
                        np.zeros((1, bm, bk))]).astype(np.float32))
    rows = jnp.asarray(np.sort(rng.integers(0, rb_rep, s_rep))
                       .astype(np.int32))
    cols = jnp.asarray(rng.integers(0, cb_rep, s_rep).astype(np.int32))
    sel = jnp.asarray(np.arange(s_rep, dtype=np.int32))
    h = jnp.asarray(rng.standard_normal((cb_rep * bk, d_rep))
                    .astype(np.float32))

    best: tuple[float, SpmmConfig] | None = None
    interpret = False
    if backend in ("jnp", "stream"):
        import functools

        import jax
        for chunk in CHUNK_CANDIDATES:
            # Operands must be ARGUMENTS of the jitted fn (a zero-arg jit
            # would let XLA constant-fold the sweep away).
            jitted = jax.jit(functools.partial(
                spmm_stream, n_row_blocks=rb_rep, bm=bm, bk=bk,
                chunk=chunk))
            fn = lambda f=jitted: f(blocks, sel, rows, cols, h)  # noqa: E731
            us = _bench(fn) * 1e6
            cfg = SpmmConfig(bd=default_config(d).bd, chunk=chunk,
                             source="swept", backend="stream")
            if best is None or us < best[0]:
                best = (us, cfg)
    elif backend == "dense":
        import functools

        import jax

        from repro.kernels.dense_spmm import dense_spmm
        # No tunable knob: the lowering is one scatter + one matmul. It is
        # still timed so get_or_tune_auto can rank it against the others.
        jitted = jax.jit(functools.partial(
            dense_spmm, n_row_blocks=rb_rep, bm=bm, bk=bk))
        fn = lambda: jitted(blocks, sel, rows, cols, h)  # noqa: E731
        us = _bench(fn) * 1e6
        best = (us, SpmmConfig(bd=default_config(d).bd, chunk=DEFAULT_CHUNK,
                               source="swept", backend="dense"))
    elif backend in ("pallas", "pallas_interpret"):
        from repro.kernels import ops as kops
        from repro.sparse.bcoo import host_row_ptr
        kops.require_tpu(backend)
        interpret = backend == "pallas_interpret"
        rptr = jnp.asarray(host_row_ptr(np.asarray(rows), rb_rep))
        # Timed at the padded width the kernel really runs at (never
        # clipped), so every candidate divides it.
        dp = padded_width(d)
        h = jnp.asarray(rng.standard_normal((cb_rep * bk, dp))
                        .astype(np.float32))
        cands = [bd for bd in BD_CANDIDATES if dp % bd == 0]
        for bd in cands:
            fn = lambda b=bd: kops.bcoo_spmm(  # noqa: E731
                blocks, sel, rows, cols, h, n_row_blocks=rb_rep,
                bm=bm, bk=bk, bd=b, row_ptr=rptr, interpret=interpret)
            us = _bench(fn, iters=1 if interpret else 3) * 1e6
            cfg = SpmmConfig(bd=bd, chunk=DEFAULT_CHUNK, source="swept",
                             backend="pallas")
            if best is None or us < best[0]:
                best = (us, cfg)
    else:
        raise ValueError(f"unknown SpMM backend {backend!r}")
    # raw requested name ("jnp", "pallas_interpret", ...): provenance says
    # what was timed; get() canonicalizes when serving the dispatch choice
    prov = {"backend": backend,
            "platform": _current_platform(),
            "device": _current_device_kind(),
            "interpret": bool(interpret)}
    return best[1], best[0], prov
