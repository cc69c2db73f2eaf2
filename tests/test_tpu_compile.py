"""Ahead-of-time compiles for a described TPU v5e, with no chip attached.

The TPU compiler refuses what the Pallas interpreter accepts (slices not
aligned to the 128-lane tiling, block shapes not divisible by 128), so the
main path's kernels and its train step are compiled here at the real
widths and shapes: synthetic ``reddit`` at scale 0.1 with 128x128 tiles
(182 row blocks, 33,117 tiles), GCN with hidden 256, 602 input features
and 41 classes. Nothing runs; only the compiler is exercised.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every test worker
imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.plan import SamplePlan
from repro.kernels import ops
from repro.models.gnn import MODELS
from repro.models.gnn.common import GraphOperands
from repro.sparse.bcoo import BlockCOO
from repro.train.optimizer import Adam
from repro.train.steps import make_gnn_steps

BLOCK = 128
ROW_BLOCKS = 182          # ceil(23,296 nodes / 128)
TILES = 33_117            # nonzero 128x128 tiles of the normalized adjacency
PLAN_PAD = 4_140          # a budget-0.1 plan, padded to 2 x ceil(TILES / 16)
FEATURES, HIDDEN, CLASSES, LAYERS = 602, 256, 41, 3
HBM_BYTES = 16 * 2 ** 30


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around these.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _operand(sharding) -> BlockCOO:
    n = ROW_BLOCKS * BLOCK
    return BlockCOO(
        blocks=_spec(sharding, (TILES + 1, BLOCK, BLOCK)),
        row_ids=_spec(sharding, (TILES,), jnp.int32),
        col_ids=_spec(sharding, (TILES,), jnp.int32),
        bm=BLOCK, bk=BLOCK, n_rows=n, n_cols=n,
        n_row_blocks=ROW_BLOCKS, n_col_blocks=ROW_BLOCKS, s_total=TILES,
        row_ptr=_spec(sharding, (ROW_BLOCKS + 1,), jnp.int32))


@pytest.mark.parametrize("d", [256, CLASSES, FEATURES])
def test_spmm_kernel_compiles_for_v5e(one_chip, d):
    """GCN hidden width, GCN output width and GraphSAGE's raw-feature
    width: the kernel compiles, with the fused epilogue, and is a Mosaic
    custom call in the program."""
    a = _operand(one_chip)
    n = ROW_BLOCKS * BLOCK

    def spmm(blocks, sel, rows, cols, rptr, h, bias, residual):
        return ops.bcoo_spmm(blocks, sel, rows, cols, h,
                             n_row_blocks=ROW_BLOCKS, bm=BLOCK, bk=BLOCK,
                             row_ptr=rptr, bias=bias, residual=residual,
                             relu=True)

    compiled = jax.jit(spmm).lower(
        a.blocks, a.row_ids, a.row_ids, a.col_ids, a.row_ptr,
        _spec(one_chip, (n, d)), _spec(one_chip, (d,)),
        _spec(one_chip, (n, d))).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("d", [256, CLASSES, FEATURES])
def test_spmm_kernel_compiles_for_v5e_on_a_plan(one_chip, d):
    """The sampled backward's shape: a budget-0.1 plan of ``PLAN_PAD``
    entries (sentinel padding included) over the full tile array, so the
    grouped walk's tail path is compiled at each width too."""
    a = _operand(one_chip)
    n = ROW_BLOCKS * BLOCK

    def spmm(blocks, sel, rows, cols, rptr, h):
        return ops.bcoo_spmm(blocks, sel, rows, cols, h,
                             n_row_blocks=ROW_BLOCKS, bm=BLOCK, bk=BLOCK,
                             row_ptr=rptr)

    plan = _spec(one_chip, (PLAN_PAD,), jnp.int32)
    compiled = jax.jit(spmm).lower(
        a.blocks, plan, plan, plan, a.row_ptr,
        _spec(one_chip, (n, d))).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("mode", ["rsc", "exact"])
def test_gcn_train_step_compiles_for_v5e(one_chip, mode):
    """The jitted full-batch GCN train step (Pallas backend, sampled
    backward under a budget-0.1 plan for ``rsc``) compiles for one v5e
    chip and fits its HBM."""
    s = lambda shape, dtype=jnp.float32: _spec(one_chip, shape, dtype)  # noqa: E731
    n = ROW_BLOCKS * BLOCK
    gcn = MODELS["gcn"]
    names = gcn.spmm_names(LAYERS)
    dims = gcn.spmm_dims(LAYERS, HIDDEN, CLASSES)
    graph = GraphOperands(
        a=_operand(one_chip), at=_operand(one_chip),
        am=_operand(one_chip), amt=_operand(one_chip),
        features=s((n, FEATURES)), labels=s((n,), jnp.int32),
        train_mask=s((n,), jnp.bool_), val_mask=s((n,), jnp.bool_),
        test_mask=s((n,), jnp.bool_), n_valid=s((), jnp.int32),
        num_classes=CLASSES, multilabel=False)
    opt = Adam(lr=0.01)
    params = jax.eval_shape(lambda: gcn.init(
        jax.random.PRNGKey(0), FEATURES, HIDDEN, CLASSES, LAYERS, True))
    opt_state = jax.eval_shape(opt.init, params)
    params, opt_state = jax.tree.map(
        lambda x: s(x.shape, x.dtype), (params, opt_state))
    key = s((2,), jnp.uint32)
    rsc_step, exact_step, _ = make_gnn_steps(
        gcn, opt, dims, names, dropout=0.5, backend="pallas")
    if mode == "rsc":
        plans = {k: SamplePlan(
            sel=s((PLAN_PAD,), jnp.int32), row_ids=s((PLAN_PAD,), jnp.int32),
            col_ids=s((PLAN_PAD,), jnp.int32), n_active=s((), jnp.int32),
            s_pad=PLAN_PAD, row_ptr=s((ROW_BLOCKS + 1,), jnp.int32))
            for k in names}
        lowered = jax.jit(rsc_step).lower(params, opt_state, graph, plans,
                                          key)
    else:
        lowered = jax.jit(exact_step).lower(params, opt_state, graph, key)
    compiled = lowered.compile()
    # one forward and one backward kernel per layer
    assert compiled.as_text().count("tpu_custom_call") >= 2 * LAYERS
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes) < HBM_BYTES
