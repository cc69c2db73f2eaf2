"""CLI training driver.

GNN, full-batch (the paper's models):
    PYTHONPATH=src python -m repro.launch.train gnn --model gcn \
        --dataset reddit --scale 0.01 --rsc --budget 0.1 --epochs 100

GNN, minibatch (GraphSAINT subgraph pool + per-subgraph RSC caches):
    PYTHONPATH=src python -m repro.launch.train gnn --minibatch \
        --dataset ogbn-products --scale 0.002 --rsc --subgraphs 16

GNN, data-parallel minibatch (mesh-sharded subgraph pool, gradients
all-reduced each step, optional int8 error-feedback compression; on a CPU
host simulate devices with --force-host-devices N):
    PYTHONPATH=src python -m repro.launch.train gnn --minibatch --dp 4 \
        --force-host-devices 4 --dataset reddit --rsc --subgraphs 8 \
        --compress-grads

LM (assigned architectures; reduced dims on CPU via --smoke):
    PYTHONPATH=src python -m repro.launch.train lm --arch qwen2-0.5b \
        --smoke --steps 50
"""
from __future__ import annotations

import argparse
import json
import sys
import time


def _maybe_force_host_devices() -> None:
    """Apply --force-host-devices BEFORE anything imports jax.

    XLA reads the flag at backend initialization, so it must be in the
    environment before the first jax import — argparse runs far too late.
    """
    from repro.launch.hostdev import force_host_devices

    for i, arg in enumerate(sys.argv):
        if arg == "--force-host-devices":
            if i + 1 >= len(sys.argv):
                raise SystemExit("--force-host-devices needs a value")
            force_host_devices(int(sys.argv[i + 1]))
            return
        if arg.startswith("--force-host-devices="):
            force_host_devices(int(arg.split("=", 1)[1]))
            return


_maybe_force_host_devices()

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro import obs  # noqa: E402
from repro.checkpoint.checkpointer import Checkpointer
from repro.configs import get_arch, make_batch, smoke_config
from repro.graphs.datasets import DATASETS, load_dataset
from repro.launch.compile_cache import enable_compile_cache
from repro.models.lm.backbone import init_params
from repro.pipeline import MinibatchConfig, MinibatchTrainer
from repro.train.lm_steps import make_train_step
from repro.train.loop import GNNTrainer, TrainConfig
from repro.train.optimizer import Adam


def run_gnn(args) -> dict:
    from repro.obs import slo as slo_mod

    ob = obs.setup_from_args(args)
    monitor = slo_mod.monitor_from_args(args)
    if monitor is not None:
        # p99_ms falls through to engine.step_ms when no serving tier
        # publishes request latencies — the training-loop objective.
        monitor.start(period=0.25)
        if ob.exporter is not None:
            ob.exporter.attach(slo=monitor)
    spec = DATASETS[args.dataset]
    g = load_dataset(args.dataset, scale=args.scale, seed=args.seed)
    common = dict(
        model=args.model, n_layers=args.layers, hidden=args.hidden,
        epochs=args.epochs, lr=args.lr, dropout=args.dropout,
        metric=spec.metric, rsc=args.rsc, budget=args.budget,
        caching=not args.no_caching, switching=not args.no_switching,
        strategy=args.strategy, block=args.block, seed=args.seed,
        backend=args.backend, eval_mode=args.eval_mode,
        stream_partitions=args.stream_partitions,
        stream_budget_mb=args.stream_budget_mb,
        stream_resident_mb=args.stream_resident_mb,
        stream_overlap=args.stream_overlap,
        strict_compiles=args.strict_compiles,
        strict_budget=args.strict_budget,
        probe_every=args.probe_every, probe_rows=args.probe_rows)
    extra: dict = {}
    if (args.dp > 1 or args.mesh) and not args.minibatch:
        raise SystemExit("--dp/--mesh require --minibatch (the sharded "
                         "source partitions the subgraph pool)")
    if args.compress_grads and not (args.dp > 1 or args.mesh):
        raise SystemExit("--compress-grads compresses the data-parallel "
                         "all-reduce; it needs --dp N (or --mesh)")
    if args.overlap_allreduce and not (args.dp > 1 or args.mesh):
        raise SystemExit("--overlap-allreduce buckets the data-parallel "
                         "all-reduce; it needs --dp N (or --mesh)")
    if args.minibatch:
        mesh = None
        if args.mesh:
            from repro.launch.mesh import parse_mesh_spec
            mesh = parse_mesh_spec(args.mesh)
            if "data" not in mesh.axis_names:
                raise SystemExit(f"--mesh {args.mesh!r} lacks a 'data' "
                                 "axis (the sharded pool axis)")
            mesh_dp = int(mesh.shape["data"])
            if args.dp and args.dp != mesh_dp:
                raise SystemExit(
                    f"--dp {args.dp} contradicts --mesh {args.mesh!r} "
                    f"(data axis = {mesh_dp})")
            args.dp = mesh_dp
        cfg = MinibatchConfig(
            n_subgraphs=args.subgraphs, method=args.pool_method,
            roots=args.roots, walk_length=args.walk_length,
            n_buckets=args.buckets, prefetch=not args.no_prefetch,
            autotune=not args.no_autotune,
            saint_norm=not args.no_saint_norm,
            dp=args.dp, compress_grads=args.compress_grads,
            overlap_allreduce=args.overlap_allreduce,
            **common)
        tr = MinibatchTrainer(cfg, g, mesh=mesh)
    else:
        tr = GNNTrainer(TrainConfig(**common), g)
    t0 = time.perf_counter()
    res = tr.train(verbose=args.verbose)
    res["wall_s"] = time.perf_counter() - t0
    if args.minibatch:
        extra = {"minibatch": True, "pool": args.pool_method,
                 "subgraphs": args.subgraphs,
                 "n_buckets": res["n_buckets"],
                 "compiles": res["compiles"],
                 "plan_hit_rate": res["plan_hit_rate"]}
        if args.dp > 1:
            planner = tr.engine.planner
            extra["dp"] = args.dp
            extra["compress_grads"] = args.compress_grads
            extra["overlap_allreduce"] = args.overlap_allreduce
            if hasattr(planner, "per_shard_summary"):
                extra["shards"] = planner.per_shard_summary()
    if monitor is not None:
        monitor.stop()
        extra["slo"] = monitor.report()
        monitor.check(where="train gnn", hard_fail=args.strict_slo)
    snap = obs.finalize_from_args(args)
    if snap is not None:
        extra["metrics"] = snap
    if res.get("ledger") is not None:
        extra["ledger"] = res["ledger"]
    print(json.dumps({
        "model": args.model, "dataset": args.dataset,
        "rsc": args.rsc, "budget": args.budget,
        "best_test": res["best_test"], "wall_s": round(res["wall_s"], 2),
        "flops_fraction": res["flops_fraction"],
        **extra,
    }))
    return res


def run_lm(args) -> dict:
    obs.setup_from_args(args)
    cfg = smoke_config(args.arch) if args.smoke else get_arch(args.arch)
    key = jax.random.PRNGKey(args.seed)
    params = init_params(key, cfg)
    opt = Adam(lr=args.lr, clip_norm=1.0)
    opt_state = opt.init(params)
    rsc = {"keep_frac": args.rsc_keep} if args.rsc else None
    step = jax.jit(make_train_step(cfg, opt, args.microbatches, rsc=rsc))
    ckpt = Checkpointer(args.ckpt_dir, keep=2) if args.ckpt_dir else None

    start = 0
    if ckpt and ckpt.latest_step() is not None:
        start, (params, opt_state) = ckpt.restore((params, opt_state))
        print(f"[train] resumed from step {start}")

    losses = []
    for i in range(start, args.steps):
        batch = make_batch(cfg, "train_4k", args.batch, args.seq, seed=i)
        t0 = time.perf_counter()
        params, opt_state, loss = step(params, opt_state, batch)
        loss = float(loss)
        losses.append(loss)
        if args.verbose and i % 10 == 0:
            print(f"step {i:4d} loss {loss:.4f} "
                  f"({time.perf_counter() - t0:.2f}s)")
        if ckpt and (i + 1) % args.ckpt_every == 0:
            ckpt.save(i + 1, (params, opt_state))
    if ckpt:
        ckpt.save(args.steps, (params, opt_state))
        ckpt.wait()
    assert np.isfinite(losses[-1])
    snap = obs.finalize_from_args(args)
    out = {"arch": cfg.name, "final_loss": losses[-1],
           "first_loss": losses[0], "steps": len(losses)}
    if snap is not None:
        out["metrics"] = snap
    print(json.dumps(out))
    return {"losses": losses, "params": params}


def main(argv: list[str] | None = None) -> dict:
    """Parse ``argv`` (default: the command line), run, return the result."""
    ap = argparse.ArgumentParser()
    sub = ap.add_subparsers(dest="cmd", required=True)

    g = sub.add_parser("gnn")
    g.add_argument("--model", default="gcn",
                   choices=["gcn", "graphsage", "gcnii"])
    g.add_argument("--dataset", default="reddit", choices=sorted(DATASETS))
    g.add_argument("--scale", type=float, default=0.005)
    g.add_argument("--layers", type=int, default=3)
    g.add_argument("--hidden", type=int, default=256)
    g.add_argument("--epochs", type=int, default=200)
    g.add_argument("--lr", type=float, default=0.01)
    g.add_argument("--dropout", type=float, default=0.5)
    g.add_argument("--rsc", action="store_true")
    g.add_argument("--budget", type=float, default=0.1)
    g.add_argument("--no-caching", action="store_true")
    g.add_argument("--no-switching", action="store_true")
    g.add_argument("--strategy", default="greedy",
                   choices=["greedy", "uniform"])
    g.add_argument("--block", type=int, default=64)
    g.add_argument("--backend", default="jnp")
    g.add_argument("--eval-mode", default="auto",
                   choices=["auto", "stream"],
                   help="'stream' evaluates with exact streaming "
                        "full-graph inference (repro/infer) instead of "
                        "the source's pooled/dense evaluator")
    g.add_argument("--stream-partitions", type=int, default=0,
                   help="explicit streaming-eval partition count "
                        "(0 = size by --stream-budget-mb)")
    g.add_argument("--stream-budget-mb", type=float, default=256.0,
                   help="device-memory budget per streaming-eval "
                        "partition")
    g.add_argument("--stream-resident-mb", type=float, default=0.0,
                   help="device-resident partition LRU budget for "
                        "streaming eval (0 = re-upload tiles every layer)")
    g.add_argument("--stream-overlap", action="store_true",
                   help="double-buffer streaming-eval partition uploads "
                        "against the device SpMM")
    g.add_argument("--minibatch", action="store_true",
                   help="GraphSAINT subgraph-pool training (pipeline/)")
    g.add_argument("--subgraphs", type=int, default=8)
    g.add_argument("--pool-method", default="random_walk",
                   choices=["random_walk", "ldg"])
    g.add_argument("--roots", type=int, default=200)
    g.add_argument("--walk-length", type=int, default=4)
    g.add_argument("--buckets", type=int, default=2)
    g.add_argument("--no-prefetch", action="store_true")
    g.add_argument("--no-autotune", action="store_true",
                   help="skip per-bucket SpMM tile sweeps at startup")
    g.add_argument("--no-saint-norm", action="store_true",
                   help="disable GraphSAINT loss/aggregator bias "
                        "correction on sampled pools")
    g.add_argument("--dp", type=int, default=0,
                   help="data-parallel degree: shard the subgraph pool "
                        "over a ('data',) mesh of N devices")
    g.add_argument("--mesh", default="",
                   help="explicit mesh spec, e.g. 'data:4' (default: "
                        "('data',) mesh of --dp devices)")
    g.add_argument("--compress-grads", action="store_true",
                   help="int8 error-feedback compression on the DP "
                        "gradient all-reduce (switch-back applies)")
    g.add_argument("--overlap-allreduce", action="store_true",
                   help="bucket the DP gradient all-reduce (one pmean "
                        "per bucket) so communication overlaps the "
                        "backward tail; trajectory-identical")
    g.add_argument("--force-host-devices", type=int, default=0,
                   help="simulate N CPU devices (sets XLA_FLAGS before "
                        "jax initializes)")
    g.add_argument("--seed", type=int, default=0)
    g.add_argument("--verbose", action="store_true")
    g.add_argument("--strict-compiles", action="store_true",
                   help="hard-fail (RetraceError) when a jitted step "
                        "compiles more often than the one-compile-per-"
                        "bucket invariant allows")
    g.add_argument("--strict-budget", action="store_true",
                   help="hard-fail (BudgetError) when an allocator run "
                        "exceeds its FLOPs budget (the approximation "
                        "ledger's conservation invariant)")
    g.add_argument("--probe-every", type=int, default=1, metavar="N",
                   help="run exact-vs-sampled error probes every N "
                        "epochs when metrics/ledger are on (0 disables)")
    g.add_argument("--probe-rows", type=int, default=8, metavar="R",
                   help="row blocks per error probe")
    obs.add_cli_flags(g)
    from repro.obs import slo as _slo
    _slo.add_cli_flags(g)
    g.set_defaults(fn=run_gnn)

    l = sub.add_parser("lm")
    l.add_argument("--arch", required=True)
    l.add_argument("--smoke", action="store_true")
    l.add_argument("--steps", type=int, default=50)
    l.add_argument("--batch", type=int, default=2)
    l.add_argument("--seq", type=int, default=64)
    l.add_argument("--lr", type=float, default=3e-4)
    l.add_argument("--microbatches", type=int, default=1)
    l.add_argument("--rsc", action="store_true")
    l.add_argument("--rsc-keep", type=float, default=0.5)
    l.add_argument("--ckpt-dir", default=None)
    l.add_argument("--ckpt-every", type=int, default=20)
    l.add_argument("--seed", type=int, default=0)
    l.add_argument("--verbose", action="store_true")
    obs.add_cli_flags(l)
    l.set_defaults(fn=run_lm)

    args = ap.parse_args(argv)
    enable_compile_cache()
    return args.fn(args)


if __name__ == "__main__":
    main()
