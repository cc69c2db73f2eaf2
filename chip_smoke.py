#!/usr/bin/env python3
"""Smoke run of RSC GNN training on a TPU, through the normal entry points.

    python chip_smoke.py                  # one chip (the default)
    python chip_smoke.py --four-chips     # data-parallel path on four chips
    python chip_smoke.py --cpu-rehearsal  # tiny sizes on the CPU, Pallas
                                          # kernel in interpret mode

One chip: full-batch GCN (3 layers, hidden 256, Reddit's 602 features and
41 classes, the synthetic ``reddit`` graph at scale 0.1) trained for 20
epochs with RSC at budget 0.1 through ``repro.launch.train.main``, once
with the Pallas kernel and once with the streaming ``jnp`` lowering, after
one forward SpMM per lowering is checked against ``kernels/ref.py`` in
float64 on the host. ``--four-chips`` instead runs only ``--minibatch
--dp 4``: one data-parallel RSC step against the per-shard gradients
averaged on one device, then a few epochs through the CLI.

Every phase prints a JSON line. The last line of standard output is
``{"ok": true, "device": {...}}`` and appears only when every check passed.
Without ``--cpu-rehearsal`` the script exits non-zero, with no result line,
when JAX finds no TPU. Everything is generated from ``--seed``.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

FULL = {"scale": 0.1, "epochs": 20, "dp_epochs": 3}
REHEARSAL = {"scale": 0.005, "epochs": 20, "dp_epochs": 2}
N_CLASSES = 41          # reddit
BUDGET = 0.1
# One bf16 pass per f32 matmul (TPU's default precision) rounds each
# operand to 8 mantissa bits, a relative error of at most 2^-9 ≈ 2e-3 per
# product. Accumulated in f32 over random signs it stays below that in
# norm; 1e-2 leaves margin and still catches a dropped or misplaced tile.
SPMM_RTOL = 1e-2
# Data-parallel step vs the single-device mean of per-shard gradients:
# both run at the same precision and differ in summation order only.
DP_LOSS_RTOL = 1e-4
DP_NORMS_RTOL = 1e-3
# The averaged gradient is read back from Adam's first moment, m = (1-b1)·g
# after one step from zeros, and compared in norm over all parameters. The
# parameter update itself, lr·g/(|g|+eps), is no fit: a gradient entry
# within rounding of zero can flip the sign of its whole update.
DP_GRAD_RTOL = 1e-2


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


class CompileClock:
    """Sums XLA backend compile seconds reported by JAX's monitoring."""

    def __init__(self):
        import jax.monitoring
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event.endswith("backend_compile_duration"):
            self.seconds += duration

    def take(self) -> float:
        s, self.seconds = self.seconds, 0.0
        return s


# ----------------------------------------------------------------- one chip

def spmm_check(sizes: dict, seed: int, backend: str) -> dict:
    """One exact forward SpMM over the training operand at d=256 and d=41,
    compared on sampled row blocks with ``kernels/ref.py`` in float64."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.rsc_spmm import exact_plan, spmm_apply
    from repro.graphs.datasets import load_dataset
    from repro.kernels.ref import bcoo_spmm_ref
    from repro.sparse.bcoo import csr_to_bcoo_host, degree_sort_permutation
    from repro.sparse.topology import sym_normalize

    g = load_dataset("reddit", scale=sizes["scale"], seed=seed)
    adj = g.adj.permute(degree_sort_permutation(g.adj))
    host, _ = csr_to_bcoo_host(sym_normalize(adj), 128, 128)
    a = host.to_device()
    plan = exact_plan(a)
    nrb = host.n_row_blocks
    rows = np.unique(np.linspace(0, nrb - 1, 8).astype(np.int64))
    idx = np.concatenate([np.arange(host.row_ptr[r], host.row_ptr[r + 1])
                          for r in rows])
    sub_rows = np.searchsorted(rows, host.row_ids[idx])
    out_rows = (rows[:, None] * 128 + np.arange(128)).reshape(-1)
    cpu = jax.devices("cpu")[0]
    rng = np.random.default_rng(seed)
    errs = {}
    for d in (256, N_CLASSES):
        h = rng.standard_normal((host.n_cols, d)).astype(np.float32)
        out = spmm_apply(a.blocks, plan, jnp.asarray(h), nrb, 128, 128,
                         backend)
        out = np.asarray(jax.block_until_ready(out))
        with jax.enable_x64(True), jax.default_device(cpu):
            ref = np.asarray(bcoo_spmm_ref(
                jnp.asarray(host.blocks[idx], jnp.float64),
                jnp.arange(idx.size), jnp.asarray(sub_rows),
                jnp.asarray(host.col_ids[idx]),
                jnp.asarray(h, jnp.float64),
                n_row_blocks=rows.size, bm=128, bk=128))
        check(out.shape == (host.n_rows, d), f"spmm d={d}: shape {out.shape}")
        check(bool(np.all(np.isfinite(out))), f"spmm d={d}: non-finite")
        got = out[out_rows].astype(np.float64)
        err = float(np.linalg.norm(got - ref) / np.linalg.norm(ref))
        errs[d] = err
        check(err <= SPMM_RTOL,
              f"spmm {backend} d={d}: relative error {err} > {SPMM_RTOL}")
    del a, plan
    return {"phase": "spmm_check", "backend": backend, "tiles": host.s_total,
            "row_blocks": nrb, "rows_checked": int(rows.size),
            "rel_err": {str(k): v for k, v in errs.items()},
            "rtol": SPMM_RTOL}


def train_phase(sizes: dict, seed: int, backend: str,
                clock: CompileClock) -> dict:
    """Full-batch RSC training through ``repro.launch.train.main``."""
    import numpy as np

    from repro.launch import train

    argv = ["gnn", "--model", "gcn", "--dataset", "reddit",
            "--scale", str(sizes["scale"]), "--layers", "3",
            "--hidden", "256", "--block", "128", "--rsc",
            "--budget", str(BUDGET), "--epochs", str(sizes["epochs"]),
            "--seed", str(seed), "--backend", backend]
    clock.take()
    t0 = time.perf_counter()
    res = train.main(argv)
    wall = time.perf_counter() - t0
    hist = res["history"]
    losses = np.asarray(hist["loss"])
    modes = hist["mode"]
    check(losses.size == sizes["epochs"], f"{backend}: {losses.size} steps")
    check(bool(np.all(np.isfinite(losses))), f"{backend}: non-finite loss")
    check(losses[-1] < losses[0],
          f"{backend}: loss did not fall ({losses[0]} -> {losses[-1]})")
    check(res["best_test"] > 1.0 / N_CLASSES,
          f"{backend}: best_test {res['best_test']} at chance")
    check(res["flops_fraction"] <= BUDGET,
          f"{backend}: flops_fraction {res['flops_fraction']} > {BUDGET}")
    check("rsc" in modes and modes[-1] == "exact",
          f"{backend}: schedule did not run RSC then switch back")
    step_s = np.asarray(hist["step_time"])
    steady = {m: float(np.median(step_s[1:][np.asarray(modes[1:]) == m]))
              for m in ("rsc", "exact") if m in modes[1:]}
    return {"phase": "train", "backend": backend, "wall_s": wall,
            "compile_s": clock.take(), "first_step_s": float(step_s[0]),
            "median_step_s": steady, "rsc_steps": modes.count("rsc"),
            "exact_steps": modes.count("exact"),
            "loss_first": float(losses[0]), "loss_last": float(losses[-1]),
            "best_test": res["best_test"],
            "flops_fraction": res["flops_fraction"]}


def autotune_provenance() -> dict:
    from repro.kernels import autotune

    cache = autotune.get_cache()
    entries = {k: bool(e.get("interpret")) for k, e in cache.entries.items()}
    check(not any(entries.values()) and cache.stats.interpret_served == 0,
          f"autotune served interpret-mode entries: {entries}")
    return {"phase": "autotune", "entries_interpret": entries,
            "lookups": cache.stats.lookups, "hits": cache.stats.hits,
            "defaults": cache.stats.defaults}


def one_chip(sizes: dict, seed: int, pallas: str, clock) -> None:
    for backend in (pallas, "jnp"):
        emit(spmm_check(sizes, seed, backend))
        gc.collect()
    for backend in (pallas, "jnp"):
        emit(train_phase(sizes, seed, backend, clock))
        gc.collect()
    emit(autotune_provenance())


# -------------------------------------------------------------- four chips

def dp_step_check(sizes: dict, seed: int) -> dict:
    """One data-parallel RSC step vs the per-shard gradients averaged on
    one device (the reference of tests/test_sharding_multidevice.py)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.graphs.datasets import load_dataset
    from repro.models.gnn import MODELS
    from repro.pipeline import (MinibatchConfig, MinibatchTrainer,
                                device_operands, stacked_operands)
    from repro.train.steps import make_gnn_grads

    g = load_dataset("reddit", scale=sizes["scale"], seed=seed)
    cfg = MinibatchConfig(model="gcn", n_layers=3, hidden=256, block=128,
                          rsc=True, budget=BUDGET, n_subgraphs=8, dp=4,
                          autotune=False, seed=seed, epochs=1)
    tr = MinibatchTrainer(cfg, g)
    eng, pool = tr.engine, tr.pool
    module = MODELS[cfg.model]
    names = module.spmm_names(cfg.n_layers)
    dims = module.spmm_dims(cfg.n_layers, cfg.hidden, pool.num_classes)
    rsc_grads, _, _ = make_gnn_grads(module, dims, names,
                                     dropout=cfg.dropout, backend=cfg.backend)
    rsc_grads = jax.jit(rsc_grads)

    sids = eng.source.epoch_schedule(0)[0]
    ops = stacked_operands(pool, [pool.subgraphs[i] for i in sids],
                           eng.runner.mesh)
    plans = eng.planner.plans_for(sids, 0, eng.schedule)
    _, sub = jax.random.split(jax.random.PRNGKey(cfg.seed + 1))
    p0, o0 = eng.params, eng.opt_state
    _, o1, lv, norms = eng.runner.rsc_step(p0, o0, ops, plans, sub, False)

    shard_devs = {id(x): [s.device for s in x.addressable_shards]
                  for x in jax.tree.leaves(ops)}
    for devs in shard_devs.values():
        check(len(set(devs)) == 4, f"operand shards on {devs}")
    norm_devs = {s.device for x in jax.tree.leaves(norms)
                 for s in x.addressable_shards}
    check(len(norm_devs) == 4, f"norms on {norm_devs}")

    keys = jax.random.split(sub, 4)
    per, losses, norms_ref = [], [], []
    for i, sid in enumerate(sids):
        plans_i = jax.tree.map(lambda x: x[i], plans)
        l_i, g_i, n_i = rsc_grads(p0, device_operands(pool, pool.subgraphs[sid]),
                                  plans_i, keys[i])
        losses.append(float(l_i))
        per.append(g_i)
        norms_ref.append(n_i)
    mean = jax.tree.map(lambda *xs: sum(xs) / len(xs), *per)
    _, o_ref = eng.opt.update(mean, o0, p0)

    def flat(t):
        return np.concatenate([np.asarray(x, np.float64).ravel()
                               for x in jax.tree.leaves(t)])
    m_dp, m_ref = flat(o1["m"]), flat(o_ref["m"])
    grad_err = float(np.linalg.norm(m_dp - m_ref) / np.linalg.norm(m_ref))
    loss_ref = float(np.mean(losses))
    loss_err = abs(float(lv) - loss_ref) / abs(loss_ref)
    n_dp = flat(norms)
    n_ref = flat(jax.tree.map(lambda *xs: jnp.stack(xs), *norms_ref))
    norms_err = float(np.max(np.abs(n_dp - n_ref)) / np.max(np.abs(n_ref)))
    check(loss_err <= DP_LOSS_RTOL, f"dp loss rel err {loss_err}")
    check(norms_err <= DP_NORMS_RTOL, f"dp norms rel err {norms_err}")
    check(grad_err <= DP_GRAD_RTOL, f"dp gradient rel err {grad_err}")
    return {"phase": "dp_step_check", "shards": len(sids),
            "devices": sorted(str(d) for d in norm_devs),
            "loss_rel_err": loss_err, "norms_rel_err": norms_err,
            "grad_rel_err": grad_err,
            "rtol": {"loss": DP_LOSS_RTOL, "norms": DP_NORMS_RTOL,
                     "grad": DP_GRAD_RTOL}}


def dp_train_phase(sizes: dict, seed: int, clock: CompileClock) -> dict:
    import numpy as np

    from repro.launch import train

    argv = ["gnn", "--minibatch", "--dp", "4", "--model", "gcn",
            "--dataset", "reddit", "--scale", str(sizes["scale"]),
            "--layers", "3", "--hidden", "256", "--block", "128",
            "--subgraphs", "8", "--rsc", "--budget", str(BUDGET),
            "--epochs", str(sizes["dp_epochs"]), "--no-autotune",
            "--seed", str(seed)]
    clock.take()
    t0 = time.perf_counter()
    res = train.main(argv)
    wall = time.perf_counter() - t0
    losses = np.asarray(res["history"]["loss"])
    check(losses.size > 0 and bool(np.all(np.isfinite(losses))),
          "dp: non-finite or missing losses")
    check(res["best_test"] > 1.0 / N_CLASSES,
          f"dp: best_test {res['best_test']} at chance")
    return {"phase": "dp_train", "wall_s": wall, "compile_s": clock.take(),
            "steps": int(losses.size), "loss_first": float(losses[0]),
            "loss_last": float(losses[-1]), "best_test": res["best_test"]}


def four_chips(sizes: dict, seed: int, clock) -> None:
    import jax

    check(len(jax.devices()) >= 4, f"{len(jax.devices())} devices < 4")
    emit(dp_step_check(sizes, seed))
    gc.collect()
    emit(dp_train_phase(sizes, seed, clock))


# --------------------------------------------------------------------- main

def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the data-parallel path on four chips")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="tiny sizes on the CPU, kernel in interpret mode")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no repro package under {ROOT / 'src'}",
              file=sys.stderr)
        return 2
    if args.cpu_rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if args.four_chips:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=4")
    sys.path.insert(0, str(ROOT / "src"))

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.cpu_rehearsal:
        print(f"chip_smoke: JAX found no TPU (default device {dev}); "
              "--cpu-rehearsal runs the tiny CPU rehearsal",
              file=sys.stderr)
        return 1

    from repro.kernels import autotune
    from repro.launch.compile_cache import enable_compile_cache

    emit({"phase": "setup", "compile_cache": enable_compile_cache(),
          "platform": dev.platform, "kind": dev.device_kind,
          "count": len(jax.devices())})
    sizes = REHEARSAL if args.cpu_rehearsal else FULL
    pallas = "pallas_interpret" if args.cpu_rehearsal else "pallas"
    clock = CompileClock()
    with tempfile.TemporaryDirectory() as tmp:
        # A fresh, empty tuning cache: nothing from an earlier run is read.
        autotune.reset(Path(tmp) / "spmm_autotune.json")
        try:
            if args.four_chips:
                four_chips(sizes, args.seed, clock)
            else:
                one_chip(sizes, args.seed, pallas, clock)
        except SmokeFailure as e:
            print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
            return 1
    stats = dev.memory_stats() or {}
    emit({"phase": "memory",
          "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
          "bytes_limit": stats.get("bytes_limit")})
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
