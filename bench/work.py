"""Work counts and chip peaks: the yardstick the roofline and MFU use.

Counts come from the graph's nonzeros and the model's widths, never from
the program's tile format, so a change of sparse format reads against the
same yardstick.

* One SpMM over a propagation matrix with ``nnz`` nonzeros at width ``d``:
  ``2·nnz·d`` FLOPs; ``nnz·(4 + 4)`` bytes of values and column indices,
  one read of the dense input and one write of the output (``n·d`` f32
  each).
* A sampled backward SpMM counts as ``budget`` times its exact count.
* Model FLOPs of a training step: the dense layers forward and backward
  (weight gradients everywhere, input gradients where a layer has an input
  that carries one) plus every SpMM the exact step runs, forward and
  backward, with no discount for sampling. An evaluation is the forward
  half.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

import spec

PEAKS = Path(__file__).resolve().parent / "peaks.json"
F32 = 4
INDEX = 4


class UnknownDevice(LookupError):
    pass


def peaks_for(kind: str, path: Path = PEAKS) -> dict:
    """Peak FLOP/s and bytes/s of one chip of ``kind`` (``device_kind``)."""
    table = json.loads(Path(path).read_text())["devices"]
    if kind not in table:
        raise UnknownDevice(f"no peaks for device kind {kind!r} in {path}")
    return table[kind]


def shape_of(config: dict, graph: dict) -> dict:
    """The sizes the counts need, from a configuration and its graph;
    ``nnz`` is that of the model's propagation matrix."""
    nodes = int(graph["nodes"])
    deg = np.bincount(graph["rows"], minlength=nodes)
    rows, _, _ = spec.model_module(config["model"]).normalize(
        graph["rows"], graph["cols"], deg)
    return {"model": config["model"], "n_layers": config["n_layers"],
            "hidden": config["hidden"], "classes": graph["classes"],
            "model_args": config.get("model_args", {}),
            "feat_dim": int(graph["features"].shape[1]), "nodes": nodes,
            "nnz": int(rows.shape[0])}


def spmm_widths(s: dict) -> tuple[list[int], list[int]]:
    """Widths of the forward SpMMs and of the backward SpMMs of one step
    (the model's, ``bench/models/<model>.py``, from the sizes ``s``)."""
    return spec.model_module(s["model"]).spmm_widths(s)


def spmm_work(nnz: int, n: int, d: int, frac: float = 1.0):
    """(FLOPs, bytes) of one SpMM, scaled by the sampled fraction."""
    flops = 2.0 * nnz * d
    nbytes = nnz * (F32 + INDEX) + 2.0 * n * d * F32
    return flops * frac, nbytes * frac


def least_seconds(flops: float, nbytes: float, peak: dict) -> float:
    return max(flops / peak["flops_per_s"], nbytes / peak["bytes_per_s"])


def spmm_least_seconds(s: dict, counts: dict, budget: float,
                       peak: dict) -> float:
    """Least time of every SpMM the window ran, call by call."""
    fwd, bwd = spmm_widths(s)
    nnz, n = s["nnz"], s["nodes"]

    def calls(widths, frac):
        return sum(least_seconds(*spmm_work(nnz, n, d, frac), peak)
                   for d in widths)

    train_fwd = calls(fwd, 1.0)
    return (counts["rsc_steps"] * (train_fwd + calls(bwd, budget))
            + counts["exact_steps"] * (train_fwd + calls(bwd, 1.0))
            + counts["evals"] * train_fwd)


def model_flops(s: dict, train: bool = True) -> float:
    """Model FLOPs of one training step (or one evaluation)."""
    n, nnz = s["nodes"], s["nnz"]
    maps = spec.model_module(s["model"]).dense_maps(s)
    fwd_spmm, bwd_spmm = spmm_widths(s)
    per_layer = [sum(2.0 * n * i * o for i, o in m) for m in maps]
    total = sum(per_layer) + sum(2.0 * nnz * d for d in fwd_spmm)
    if not train:
        return total
    # weight gradients of every dense map; input gradients of all but
    # the first layer's, whose input (the features) carries none
    return (total + sum(per_layer) + sum(per_layer[1:])
            + sum(2.0 * nnz * d for d in bwd_spmm))


def window_model_flops(s: dict, counts: dict) -> float:
    steps = counts["rsc_steps"] + counts["exact_steps"]
    return (steps * model_flops(s, train=True)
            + counts["evals"] * model_flops(s, train=False))
