"""GNN node-serving driver: replicated snapshot frontend + batched queries.

Builds (or quickly trains) a model, precomputes full-graph activations via
partitioned streaming inference, then stands up a :class:`ServeFrontend`
(``--replicas`` NodeServers behind a write-ahead update log and a
query-batching dispatcher) and drives concurrent queries while edge
updates rebuild replicas one at a time off the read path:

    PYTHONPATH=src python -m repro.launch.serve_gnn --dataset reddit \
        --scale 0.002 --model gcn --train-epochs 20 --queries 256 \
        --memory-budget-mb 64 --update-edges 3 --replicas 2

``--replicas 0`` falls back to a single bare NodeServer (no frontend
threads) — the PR-4 sequential path. ``--sampled-budget`` < 1 adds an
RSC-sampled replica that queries can opt into with an error budget.
With ``--ckpt-dir`` the params warm-start from the latest checkpoint of a
previous training run instead of training here.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from repro import obs
from repro.graphs.datasets import DATASETS, load_dataset
from repro.infer import NodeServer, ServeFrontend, StreamConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.models.gnn import MODELS
from repro.obs import slo as slo_mod
from repro.train.loop import GNNTrainer, TrainConfig


def get_params(args, graph):
    module = MODELS[args.model]
    if args.ckpt_dir:
        from repro.checkpoint.checkpointer import Checkpointer
        from repro.train.optimizer import Adam
        params = module.init(
            jax.random.PRNGKey(args.seed), graph.features.shape[1],
            args.hidden, graph.num_classes, args.layers, not args.no_bn)
        ck = Checkpointer(args.ckpt_dir)
        step, (params, _) = ck.restore((params, Adam().init(params)))
        print(f"[serve] restored params from step {step}")
        return params
    cfg = TrainConfig(model=args.model, n_layers=args.layers,
                      hidden=args.hidden, epochs=args.train_epochs,
                      dropout=args.dropout, batchnorm=not args.no_bn,
                      block=args.block, seed=args.seed,
                      metric=DATASETS[args.dataset].metric)
    tr = GNNTrainer(cfg, graph)
    if args.train_epochs > 0:
        res = tr.train(eval_every=max(args.train_epochs // 2, 1))
        print(f"[serve] trained {args.train_epochs} epochs, "
              f"test={res['best_test']:.4f}")
    return tr.engine.params


def random_edge_updates(graph, n: int, rng) -> list[tuple[int, int]]:
    """n random non-edges to insert (original-id pairs)."""
    adj, out = graph.adj, []
    while len(out) < n:
        u, v = (int(x) for x in rng.integers(0, graph.n, 2))
        if u == v:
            continue
        if v in adj.col[adj.rowptr[u]: adj.rowptr[u + 1]]:
            continue
        out.append((u, v))
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="reddit", choices=sorted(DATASETS))
    ap.add_argument("--scale", type=float, default=0.002)
    ap.add_argument("--model", default="gcn",
                    choices=["gcn", "graphsage", "gcnii"])
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--dropout", type=float, default=0.5)
    ap.add_argument("--no-bn", action="store_true",
                    help="disable batchnorm (incremental recompute is "
                         "exact without it; with BN stats are frozen)")
    ap.add_argument("--block", type=int, default=64)
    ap.add_argument("--backend", default="jnp")
    ap.add_argument("--train-epochs", type=int, default=10)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--memory-budget-mb", type=float, default=64.0)
    ap.add_argument("--partitions", type=int, default=0,
                    help="explicit partition count (overrides the budget)")
    ap.add_argument("--queries", type=int, default=256)
    ap.add_argument("--query-batch", type=int, default=32)
    ap.add_argument("--update-edges", type=int, default=0,
                    help="insert N random edges and recompute dirty sets")
    ap.add_argument("--replicas", type=int, default=2,
                    help="exact NodeServer replicas behind the frontend "
                         "(0 = bare single server, no frontend threads)")
    ap.add_argument("--max-batch", type=int, default=256,
                    help="max node ids coalesced into one dispatch")
    ap.add_argument("--sampled-budget", type=float, default=0.0,
                    help="add an RSC-sampled replica with this column "
                         "keep-fraction (<1); queries opt in via an "
                         "error budget (0 = exact replicas only)")
    ap.add_argument("--stream-resident-mb", type=float, default=0.0,
                    help="device-resident partition LRU budget for the "
                         "streaming forward (0 = re-upload every layer)")
    ap.add_argument("--stream-overlap", action="store_true",
                    help="double-buffer partition uploads against the "
                         "device SpMM during cache builds/rebuilds")
    ap.add_argument("--slow-log", default=None, metavar="PATH",
                    help="write the slowest-K request reservoir "
                         "(/debug/slow content) to this JSON file at exit")
    ap.add_argument("--seed", type=int, default=0)
    obs.add_cli_flags(ap)
    slo_mod.add_cli_flags(ap)
    args = ap.parse_args()
    enable_compile_cache()
    ob = obs.setup_from_args(args)
    monitor = slo_mod.monitor_from_args(args)
    if monitor is not None:
        monitor.start(period=0.25)
        if ob.exporter is not None:
            ob.exporter.attach(slo=monitor)
            print(f"[obs] slo objectives at {ob.exporter.url}/slo")

    graph = load_dataset(args.dataset, scale=args.scale, seed=args.seed)
    params = get_params(args, graph)

    cfg = StreamConfig(
        block=args.block,
        n_partitions=args.partitions or None,
        memory_budget_mb=(None if args.partitions
                          else args.memory_budget_mb),
        backend=args.backend,
        resident_mb=args.stream_resident_mb or None,
        overlap=args.stream_overlap)

    rng = np.random.default_rng(args.seed)
    updates: list[dict] = []

    def run_queries(query_fn) -> tuple[int, float]:
        t0 = time.perf_counter()
        n_batches = 0
        for start in range(0, args.queries, args.query_batch):
            ids = rng.integers(0, graph.n,
                               min(args.query_batch, args.queries - start))
            logits = query_fn(ids)
            assert logits.shape == (ids.shape[0], graph.num_classes) \
                or graph.multilabel
            n_batches += 1
        return n_batches, time.perf_counter() - t0

    if args.replicas <= 0:
        server = NodeServer(graph, args.model, params, cfg)
        n_batches, query_s = run_queries(server.query)
        if args.update_edges > 0:
            for e in random_edge_updates(graph, args.update_edges, rng):
                stats = server.update_edges(add=[e])
                updates.append(
                    {k: (round(v, 6) if isinstance(v, float) else v)
                     for k, v in stats.items() if k != "retile"})
        n_parts = server.si.n_partitions
        build_s = server.build_seconds
        serve_stats = server.stats()
    else:
        frontend = ServeFrontend(
            graph, args.model, params, cfg, replicas=args.replicas,
            max_batch=args.max_batch,
            sampled_budget=(args.sampled_budget
                            if 0 < args.sampled_budget < 1 else None))
        if ob.exporter is not None and frontend.taillog is not None:
            ob.exporter.attach(taillog=frontend.taillog)
        n_batches, query_s = run_queries(
            lambda ids: frontend.query(ids).logits)
        if args.update_edges > 0:
            for e in random_edge_updates(graph, args.update_edges, rng):
                seq = frontend.update_edges(add=[e], wait=True)
                updates.append({"seq": seq,
                                "min_applied": frontend.min_applied_seq()})
        n_parts = frontend.replicas[0].si.n_partitions
        build_s = frontend.replicas[0].build_seconds
        serve_stats = frontend.stats()
        if args.slow_log and frontend.taillog is not None:
            with open(args.slow_log, "w") as f:
                json.dump(frontend.taillog.snapshot(), f, indent=1)
            print(f"[serve] slow-request log → {args.slow_log}")
        frontend.close()

    out = {
        "dataset": args.dataset, "model": args.model,
        "n_nodes": graph.n,
        "replicas": max(args.replicas, 0),
        "n_partitions": n_parts,
        "cache_build_s": round(build_s, 4),
        "queries": int(args.queries),
        "query_batches": n_batches,
        "queries_per_s": round(args.queries / max(query_s, 1e-9), 1),
        "updates": updates,
        "serve_stats": serve_stats,
    }
    if monitor is not None:
        monitor.stop()
        out["slo"] = monitor.report()
        # Raises SLOError under --strict-slo, mirroring --strict-compiles.
        monitor.check(where="serve_gnn", hard_fail=args.strict_slo)
    snap = obs.finalize_from_args(args)
    if snap is not None:
        out["metrics"] = snap
    print(json.dumps(out))


if __name__ == "__main__":
    main()
