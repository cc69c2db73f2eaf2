"""Row-segmented SpMM engine: kernel + streaming fallback vs the
segment_sum oracle, fused epilogue, autotuner cache behavior."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.plan import build_plan, full_plan, plan_row_ptr
from repro.core.rsc_spmm import (exact_plan, rsc_spmm, spmm_apply,
                                 spmm_stream, transpose_bcoo)
from repro.kernels import autotune
from repro.kernels.bcoo_spmm import bcoo_spmm
from repro.kernels.ref import bcoo_spmm_ref
from repro.sparse.bcoo import csr_to_bcoo
from repro.sparse.topology import sym_normalize

from tests.conftest import (HAS_HYPOTHESIS, given, random_csr, settings,
                            st)


def _plan_operands(n, density, seed, bm=8, keep_frac=None):
    csr = sym_normalize(random_csr(n, density, seed=seed))
    a, meta = csr_to_bcoo(csr, bm=bm, bk=bm)
    if keep_frac is None:
        plan = full_plan(meta, a.n_row_blocks, a.s_total, bucket=4)
    else:
        keep = np.zeros(a.n_col_blocks, bool)
        keep[: max(1, int(keep_frac * a.n_col_blocks))] = True
        plan = build_plan(meta, keep, a.n_row_blocks, a.s_total, bucket=4)
    return a, plan


def _ref(a, plan, h):
    return bcoo_spmm_ref(a.blocks, plan.sel, plan.row_ids, plan.col_ids, h,
                         n_row_blocks=a.n_row_blocks, bm=a.bm, bk=a.bk)


@pytest.mark.parametrize("density,keep_frac,chunk", [
    (0.05, None, 4), (0.05, 0.5, 16), (0.2, None, 7), (0.2, 0.25, 64),
    (0.5, 0.8, 32)])
def test_stream_matches_ref(density, keep_frac, chunk):
    """Streaming fallback == segment_sum oracle across densities, sampled
    plans (sentinel padding), and chunk sizes incl. non-dividing ones."""
    a, plan = _plan_operands(64, density, seed=1, keep_frac=keep_frac)
    rng = np.random.default_rng(2)
    h = jnp.asarray(rng.standard_normal((a.n_cols, 24)).astype(np.float32))
    out = spmm_stream(a.blocks, plan.sel, plan.row_ids, plan.col_ids, h,
                      n_row_blocks=a.n_row_blocks, bm=a.bm, bk=a.bk,
                      chunk=chunk)
    np.testing.assert_allclose(np.asarray(out), np.asarray(_ref(a, plan, h)),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("density,keep_frac,bd", [
    (0.05, None, 8), (0.2, 0.5, 16), (0.4, 0.25, 16)])
def test_rowseg_kernel_matches_ref(density, keep_frac, bd):
    """Row-segmented Pallas kernel (interpret) == oracle, incl. plan
    row_ptr, sampled plans, and multi-tile d."""
    a, plan = _plan_operands(64, density, seed=3, keep_frac=keep_frac)
    rng = np.random.default_rng(4)
    h = jnp.asarray(rng.standard_normal((a.n_cols, 16)).astype(np.float32))
    out = bcoo_spmm(a.blocks, plan.sel, plan.row_ids, plan.col_ids, h,
                    n_row_blocks=a.n_row_blocks, bm=a.bm, bk=a.bk, bd=bd,
                    row_ptr=plan.row_ptr, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(_ref(a, plan, h)),
                               atol=1e-4, rtol=1e-4)


def test_kernel_empty_row_segments_zeroed():
    """row_ptr with empty segments (no tiles at all for a row block) must
    yield exactly zero — the row-segmented grid needs no sentinel entry."""
    bm = bk = 8
    blocks = jnp.asarray(np.concatenate(
        [np.ones((2, bm, bk), np.float32),
         np.zeros((1, bm, bk), np.float32)]))
    sel = jnp.asarray(np.array([0, 1], np.int32))
    rows = jnp.asarray(np.array([0, 3], np.int32))    # rows 1, 2 empty
    cols = jnp.asarray(np.array([0, 1], np.int32))
    rptr = plan_row_ptr(rows, 4)
    h = jnp.asarray(np.ones((2 * bk, 8), np.float32))
    out = np.asarray(bcoo_spmm(blocks, sel, rows, cols, h, n_row_blocks=4,
                               bm=bm, bk=bk, bd=8, row_ptr=rptr,
                               interpret=True))
    assert np.allclose(out[:bm], bk)
    assert np.allclose(out[bm:3 * bm], 0.0)
    assert np.allclose(out[3 * bm:], bk)


@pytest.mark.parametrize("backend", ["jnp", "pallas_interpret"])
@pytest.mark.parametrize("bias,residual,relu", [
    (True, False, False), (False, True, True), (True, True, True),
    (False, False, True)])
def test_epilogue_fusion_matches_composition(backend, bias, residual, relu):
    """Fused epilogue == unfused spmm-then-ops on both backends."""
    a, plan = _plan_operands(64, 0.15, seed=5)
    rng = np.random.default_rng(6)
    d = 16
    h = jnp.asarray(rng.standard_normal((a.n_cols, d)).astype(np.float32))
    b = (jnp.asarray(rng.standard_normal(d).astype(np.float32))
         if bias else None)
    r = (jnp.asarray(rng.standard_normal((a.n_rows, d)).astype(np.float32))
         if residual else None)
    out = spmm_apply(a.blocks, plan, h, a.n_row_blocks, a.bm, a.bk, backend,
                     bias=b, residual=r, relu=relu)
    ref = np.asarray(_ref(a, plan, h))
    if bias:
        ref = ref + np.asarray(b)[None, :]
    if residual:
        ref = ref + np.asarray(r)
    if relu:
        ref = np.maximum(ref, 0.0)
    np.testing.assert_allclose(np.asarray(out), ref, atol=1e-4, rtol=1e-4)


def test_epilogue_gradients_match_unfused():
    """custom_vjp through the fused epilogue == autodiff of the unfused
    composition (bias, residual/tap, relu; sampled backward exact plan)."""
    a, _ = _plan_operands(48, 0.2, seed=7)
    at = transpose_bcoo(a)
    bwd_plan = exact_plan(at)
    rng = np.random.default_rng(8)
    d = 12
    h = jnp.asarray(rng.standard_normal((a.n_cols, d)).astype(np.float32))
    b = jnp.asarray(rng.standard_normal(d).astype(np.float32))
    r = jnp.asarray(rng.standard_normal((a.n_rows, d)).astype(np.float32))

    def fused(h, b, r):
        return jnp.sum(rsc_spmm(a, at, bwd_plan, h, "jnp",
                                bias=b, residual=r, relu=True) ** 2)

    def unfused(h, b, r):
        y = rsc_spmm(a, at, bwd_plan, h, "jnp")
        return jnp.sum(jnp.maximum(y + b[None, :] + r, 0.0) ** 2)

    gf = jax.grad(fused, argnums=(0, 1, 2))(h, b, r)
    gu = jax.grad(unfused, argnums=(0, 1, 2))(h, b, r)
    for x, y in zip(gf, gu):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("d", [41, 602])
@pytest.mark.parametrize("bias,residual,relu", [
    (False, False, False), (True, True, True), (False, True, False)])
def test_padded_width_dispatch_matches_ref(d, bias, residual, relu):
    """Widths that are not a multiple of the 128-lane tile (GCN's 41
    classes, GraphSAGE's 602 raw features) dispatch through column padding
    and come back at width ``d``, equal to the oracle."""
    from repro.kernels import ops

    a, plan = _plan_operands(64, 0.2, seed=13, keep_frac=0.5)
    rng = np.random.default_rng(14)
    h = jnp.asarray(rng.standard_normal((a.n_cols, d)).astype(np.float32))
    b = (jnp.asarray(rng.standard_normal(d).astype(np.float32))
         if bias else None)
    r = (jnp.asarray(rng.standard_normal((a.n_rows, d)).astype(np.float32))
         if residual else None)
    out = ops.bcoo_spmm(a.blocks, plan.sel, plan.row_ids, plan.col_ids, h,
                        n_row_blocks=a.n_row_blocks, bm=a.bm, bk=a.bk,
                        row_ptr=plan.row_ptr, bias=b, residual=r, relu=relu,
                        interpret=True)
    ref = np.asarray(_ref(a, plan, h))
    if bias:
        ref = ref + np.asarray(b)[None, :]
    if residual:
        ref = ref + np.asarray(r)
    if relu:
        ref = np.maximum(ref, 0.0)
    assert out.shape == (a.n_rows, d)
    np.testing.assert_allclose(np.asarray(out), ref, atol=1e-4, rtol=1e-4)


def test_pallas_backend_without_tpu_raises():
    """Asking for the compiled kernel where no TPU is attached fails, at
    the entry point and at dispatch; it never becomes interpret mode."""
    from repro.kernels import ops
    from repro.train.loop import GNNTrainer, TrainConfig

    assert jax.default_backend() != "tpu"
    a, plan = _plan_operands(32, 0.2, seed=15)
    h = jnp.ones((a.n_cols, 16), jnp.float32)
    with pytest.raises(RuntimeError, match="pallas_interpret"):
        ops.require_tpu("pallas")
    with pytest.raises(ValueError, match="interpret"):
        spmm_apply(a.blocks, plan, h, a.n_row_blocks, a.bm, a.bk, "pallas")
    with pytest.raises(RuntimeError, match="pallas_interpret"):
        autotune.get_or_tune("pallas", bm=8, bk=8, d=16, s_pad=32,
                             n_row_blocks=4, n_col_blocks=4, persist=False)
    from repro.graphs.synthetic import sbm_graph
    g = sbm_graph(n_nodes=64, n_clusters=2, avg_degree=4, feat_dim=8,
                  seed=0)
    with pytest.raises(RuntimeError, match="pallas_interpret"):
        GNNTrainer(TrainConfig(backend="pallas", block=32, hidden=8,
                               n_layers=2, epochs=1), g)


if HAS_HYPOTHESIS:
    @settings(max_examples=15, deadline=None)
    @given(n=st.integers(24, 72), density=st.floats(0.02, 0.5),
           keep=st.floats(0.1, 1.0), chunk=st.integers(1, 40),
           seed=st.integers(0, 100))
    def test_stream_matches_ref_property(n, density, keep, chunk, seed):
        a, plan = _plan_operands(n, density, seed=seed, keep_frac=keep)
        rng = np.random.default_rng(seed + 1)
        h = jnp.asarray(
            rng.standard_normal((a.n_cols, 8)).astype(np.float32))
        out = spmm_stream(a.blocks, plan.sel, plan.row_ids, plan.col_ids,
                          h, n_row_blocks=a.n_row_blocks, bm=a.bm, bk=a.bk,
                          chunk=chunk)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(_ref(a, plan, h)),
            atol=1e-4, rtol=1e-4)
else:  # pragma: no cover - dev image always has hypothesis
    @pytest.mark.skip(reason="hypothesis not installed")
    def test_stream_matches_ref_property():
        pass


# ----------------------------------------------------------- autotuner

def test_autotune_second_query_is_cache_hit(tmp_path):
    cache = autotune.reset(tmp_path / "tune.json")
    kw = dict(bm=8, bk=8, d=16, s_pad=32, n_row_blocks=4, n_col_blocks=4)
    cfg1 = autotune.get_or_tune("jnp", **kw)
    assert cache.stats.sweeps == 1
    assert cfg1.source == "swept"
    cfg2 = autotune.get_or_tune("jnp", **kw)
    assert cache.stats.sweeps == 1          # no re-sweep
    assert cache.stats.hits == 1
    assert (cfg2.bd, cfg2.chunk) == (cfg1.bd, cfg1.chunk)
    # same bucket, different exact shape → still a hit (pow2 bucketing)
    autotune.get_or_tune("jnp", bm=8, bk=8, d=15, s_pad=30,
                         n_row_blocks=4, n_col_blocks=4)
    assert cache.stats.sweeps == 1
    autotune.reset()


def test_autotune_cache_persists_to_json(tmp_path):
    path = tmp_path / "tune.json"
    autotune.reset(path)
    kw = dict(bm=8, bk=8, d=16, s_pad=32, n_row_blocks=4, n_col_blocks=4)
    cfg = autotune.get_or_tune("jnp", **kw)
    assert path.exists()
    # a fresh process (new cache object) reads the persisted winner
    cache2 = autotune.reset(path)
    sig = autotune.signature("jnp", **kw)
    got = autotune.lookup(sig, d=16)
    assert got.source == "cache"
    assert (got.bd, got.chunk) == (cfg.bd, cfg.chunk)
    assert cache2.stats.sweeps == 0
    autotune.reset()


def test_autotune_lookup_never_sweeps(tmp_path):
    cache = autotune.reset(tmp_path / "tune.json")
    cfg = autotune.lookup("jnp|bm8|bk8|d16|s32|rb4|dens1", d=16)
    assert cfg.source == "default"
    assert cache.stats.sweeps == 0
    autotune.reset()


def test_signature_density_bands():
    lo = autotune.signature("jnp", bm=8, bk=8, d=16, s_pad=8,
                            n_row_blocks=16, n_col_blocks=16)
    hi = autotune.signature("jnp", bm=8, bk=8, d=16, s_pad=200,
                            n_row_blocks=16, n_col_blocks=16)
    assert lo != hi  # same shapes, different density band


def test_autotune_save_merges_concurrent_entries(tmp_path):
    """Two cache objects sharing one file must not clobber each other's
    entries: save() re-reads and merges before the atomic replace."""
    import json

    path = tmp_path / "tune.json"
    a = autotune.AutotuneCache(path)
    b = autotune.AutotuneCache(path)
    a.put("sigA", autotune.SpmmConfig(bd=128, chunk=16), us=1.0)
    b.put("sigB", autotune.SpmmConfig(bd=256, chunk=32), us=2.0)
    raw = json.loads(path.read_text())
    assert set(raw["entries"]) >= {"sigA", "sigB"}
    assert raw["entries"]["sigA"]["chunk"] == 16
    assert raw["entries"]["sigB"]["chunk"] == 32
    # writer-local precedence on conflict
    a.put("sigB", autotune.SpmmConfig(bd=512, chunk=8), us=3.0)
    raw = json.loads(path.read_text())
    assert raw["entries"]["sigB"]["chunk"] == 8
    assert "sigA" in raw["entries"]


def test_autotune_concurrent_writers_never_corrupt(tmp_path):
    """Hammer one cache file from many threads: the file must parse as
    valid JSON at every point and end up holding every entry (unique temp
    names + merge-on-save + atomic os.replace)."""
    import json
    import threading

    path = tmp_path / "tune.json"
    n_threads, per_thread = 8, 10
    errors = []

    def writer(t):
        try:
            cache = autotune.AutotuneCache(path)
            for i in range(per_thread):
                cache.put(f"sig{t}_{i}",
                          autotune.SpmmConfig(bd=128, chunk=16), us=1.0)
                json.loads(path.read_text())    # parses mid-flight
        except Exception as e:                  # pragma: no cover
            errors.append(e)

    threads = [threading.Thread(target=writer, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors
    final = json.loads(path.read_text())["entries"]
    expected = {f"sig{t}_{i}" for t in range(n_threads)
                for i in range(per_thread)}
    assert set(final) <= expected
    # whichever writer replaced last had (at least) its own full set in
    # its merged in-memory view
    assert len(final) >= per_thread
    for e in final.values():
        assert e["bd"] == 128 and e["chunk"] == 16


@pytest.mark.parametrize("backend", ["pallas_interpret", "jnp"])
def test_full_graph_publishes_grouped_share(backend):
    """The full-graph source and planner publish the kernel's tile-group
    share per layer and direction from host row pointers; a backend that
    never runs the kernel publishes none."""
    from repro import obs
    from repro.graphs.synthetic import sbm_graph
    from repro.kernels.bcoo_spmm import grouped_share
    from repro.models.gnn import MODELS
    from repro.sparse.bcoo import host_row_ptr
    from repro.train.engine import (FullGraphPlanner, FullGraphSource,
                                    TrainConfig)

    graph = sbm_graph(n_nodes=300, n_clusters=3, avg_degree=8, feat_dim=8,
                      seed=0)
    cfg = TrainConfig(model="gcn", n_layers=2, hidden=16, block=32,
                      rsc=True, backend=backend)
    gcn = MODELS["gcn"]
    obs.reset(metrics=True)
    try:
        source = FullGraphSource(graph, cfg, gcn)
        at, meta, fro = source.planner_operand()
        planner = FullGraphPlanner(cfg, gcn, at, meta, fro,
                                   source.num_classes)
        reg = obs.get_registry()
        a = source.ops.a
        fwd = grouped_share(
            host_row_ptr(source.meta.a_meta.row_ids, a.n_row_blocks), 8)
        for layer in gcn.spmm_names(2):
            ops_ = ("spmm_fwd", "spmm_bwd_exact", "spmm_bwd_sampled")
            ks = [reg.get_gauge("spmm.group_k", layer=layer, op=o)
                  for o in ops_]
            shares = [reg.get_gauge("spmm.grouped_share", layer=layer, op=o)
                      for o in ops_]
            if backend == "jnp":
                assert ks == [None] * 3 and shares == [None] * 3
                continue
            assert ks == [8, 8, 8]
            assert shares[0] == fwd
            assert shares[2] == grouped_share(
                planner.cache.ops[layer].row_ptr, 8)
    finally:
        obs.reset()
