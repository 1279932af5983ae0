"""Compile the main-path Pallas kernels for a described TPU v5e at real widths.

No chip is needed: the TPU compiler is installed, and it compiles for a
topology that is described rather than attached. It refuses what Mosaic
would refuse on the chip — blocks not aligned to the (8, 128) tiling,
scoring tiles that overflow VMEM, shape casts it cannot lay out — none of
which interpret mode sees. Each kernel test asserts that the compiled
program holds the kernel (a ``tpu_custom_call``); the cohort gather's test
asserts that the compiler kept its gathers of whole rows.

Widths: the paper's lastfm (Table 2: M=17,632 items, K=25 factors,
Theta=100, keep 0.1 -> M_s=1,763 payload rows), K=16, Theta=500 (MIND),
the serving buckets at the 131,072-item serving catalog, and the eval
block derived for each batch. Kernel modules are called directly:
``kernels/ops.py`` dispatches on ``jax.default_backend()``, which is the
CPU here.
"""
import inspect
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.federated.simulation import UserLists, _cohort_x, _list_piece
from repro.kernels import fcf_grad as fcf
from repro.kernels import moment_quant as mq
from repro.kernels import ops
from repro.kernels import payload_gather as pg
from repro.kernels import payload_quant as pq
from repro.kernels import payload_score as ps
from repro.serve.engine import ServingEngine

M, M_S = 17_632, 1_763          # lastfm items, payload rows at keep 0.1
SHARD_ROWS = M // 4             # one row block of a 4-way sharded table
SERVE_M = 131_072               # the serving catalog
TOP_N = 10


@pytest.fixture(scope="module")
def topo():
    """A described v5e:2x2, with the persistent compile cache off: entries
    compiled for a described chip cannot be read back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield topologies.get_topology_desc(platform="tpu",
                                               topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — no TPU compiler installed
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def arg(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)


def _assert_kernel(fn, *args, **kwargs):
    text = fn.lower(*args, **kwargs).compile().as_text()
    assert "tpu_custom_call" in text


# name -> (kernel, args(arg, k)): every row-granular kernel of the round
# step, the compressed-moment commit and the sharded engine's block halves
ROW_KERNELS = {
    "gather_rows": (pg.gather_rows, lambda a, k: (
        a((M, k)), a((M_S,), jnp.int32))),
    "scatter_set_rows": (pg.scatter_set_rows, lambda a, k: (
        a((M, k)), a((M_S,), jnp.int32), a((M_S, k)))),
    "scatter_add_rows": (pg.scatter_add_rows, lambda a, k: (
        a((M, k)), a((M_S,), jnp.int32), a((M_S, k)))),
    "gather_rows_block": (pg.gather_rows_block, lambda a, k: (
        a((SHARD_ROWS, k)), a((M_S,), jnp.int32))),
    "scatter_set_rows_block": (pg.scatter_set_rows_block, lambda a, k: (
        a((SHARD_ROWS, k)), a((M_S,), jnp.int32), a((M_S, k)))),
    "gather_quantize_rows": (pq.gather_quantize_rows, lambda a, k: (
        a((M, k)), a((M_S,), jnp.int32))),
    "gather_quantize_rows_block": (pq.gather_quantize_rows_block,
                                   lambda a, k: (
        a((SHARD_ROWS, k)), a((M_S,), jnp.int32))),
    "dequant_scatter_set_rows": (pq.dequant_scatter_set_rows, lambda a, k: (
        a((M, k)), a((M_S,), jnp.int32), a((M_S, k), jnp.int8),
        a((M_S, 1)))),
    "gather_dequant_rows": (mq.gather_dequant_rows, lambda a, k: (
        a((M, k), jnp.int8), a((M, 1)), a((M_S,), jnp.int32))),
    "gather_dequant_rows_block": (mq.gather_dequant_rows_block, lambda a, k: (
        a((SHARD_ROWS, k), jnp.int8), a((SHARD_ROWS, 1)),
        a((M_S,), jnp.int32))),
    "quant_scatter_set_rows": (mq.quant_scatter_set_rows, lambda a, k: (
        a((M, k), jnp.int8), a((M, 1)), a((M_S,), jnp.int32), a((M_S, k)))),
    "quant_scatter_set_rows_stochastic": (mq.quant_scatter_set_rows,
                                          lambda a, k: (
        a((M, k), jnp.int8), a((M, 1)), a((M_S,), jnp.int32), a((M_S, k)),
        a((M_S, k)))),
    "quant_scatter_set_rows_block": (mq.quant_scatter_set_rows_block,
                                     lambda a, k: (
        a((SHARD_ROWS, k), jnp.int8), a((SHARD_ROWS, 1)),
        a((M_S,), jnp.int32), a((M_S, k)), a((M_S, k)))),
}


@pytest.mark.parametrize("k", [16, 25])
@pytest.mark.parametrize("name", sorted(ROW_KERNELS))
def test_row_kernel_compiles_for_tpu(arg, name, k):
    fn, args = ROW_KERNELS[name]
    _assert_kernel(fn, *args(arg, k))


@pytest.mark.parametrize("theta", [100, 500])
def test_fcf_grad_compiles_for_tpu(arg, theta):
    k = 25
    _assert_kernel(fcf.fcf_grad, arg((M_S, k)), arg((theta, k)),
                   arg((theta, M_S)), alpha=4.0, l2=0.0, block_m=256)


# (users, items, Theta, M_s) of the benchmark's two cells: Table 2 Last.FM
# and MIND at keep 0.1
COHORT_SHAPES = {"lastfm": (1_892, M, 100, M_S),
                 "mind": (16_026, 6_923, 500, 692)}


@pytest.mark.parametrize("data", sorted(COHORT_SHAPES))
def test_cohort_gather_compiles_to_slice_gathers_for_tpu(arg, data):
    """The TPU compiler keeps the cohort block's two stages as gathers of
    whole rows (the interaction matrix's, then the slab transpose's): no
    gather of single elements, which the TPU pays for one by one."""
    users, items, theta, m_s = COHORT_SHAPES[data]

    def block(train, ids, idx):
        return _cohort_x(train, ids, 1, None)(idx)

    text = jax.jit(block).lower(arg((users, items)), arg((theta,), jnp.int32),
                                arg((m_s,), jnp.int32)).compile().as_text()
    sizes = [tuple(int(v) for v in g.split(","))
             for g in re.findall(r" gather\(.*?slice_sizes=\{([0-9,]+)\}",
                                 text)]
    assert sorted(sizes) == [(1, theta), (1, items)], sizes


# MovieLens-25M as per-user item lists: its train split's users and ids,
# items, Theta and M_s at keep 0.1
ML25M_USERS, ML25M_IDS, ML25M_ITEMS = 162_541, 20_050_397, 62_423
ML25M_THETA, ML25M_M_S = 1_000, 6_242
ML25M_CHUNK = 16                # _list_chunk of its train degrees (median 74)


def test_fcf_grad_compiles_for_tpu_at_ml25m(arg):
    k = 25
    _assert_kernel(fcf.fcf_grad, arg((ML25M_M_S, k)), arg((ML25M_THETA, k)),
                   arg((ML25M_THETA, ML25M_M_S)), alpha=4.0, l2=0.0,
                   block_m=256)


def _gathers_from(text, shape):
    """Slice sizes of the gathers whose operand is an array of ``shape``
    (a parameter of the fusion that holds the gather)."""
    defs = dict(re.findall(r"(%\S+) = (\w+\[[0-9,]*\])", text))
    return [tuple(int(v) for v in sizes.split(","))
            for operand, sizes in re.findall(
                r" gather\((%[^,\s]+), .*?slice_sizes=\{([0-9,]+)\}", text)
            if defs.get(operand) == shape]


def test_list_cohort_gather_compiles_for_tpu(arg):
    """The list gather at MovieLens-25M's size: the ids stay a parameter of
    the program, laid out in rows of the chunk width, and are read only by
    gathers of whole rows, never one id at a time; no (Theta, M) array is
    made on the way to the block, and the walk sets it as bytes (a float32
    carry crowds the round's tables out of VMEM in the whole chunk)."""
    piece = _list_piece(ML25M_THETA, ML25M_USERS, ML25M_IDS)
    chunk = ML25M_CHUNK
    rows = -(-ML25M_IDS // chunk)

    def block(indptr, indices, ids, idx):
        lists = UserLists(indptr=indptr, indices=indices,
                          num_items=ML25M_ITEMS, piece=piece)
        return _cohort_x(lists, ids, 1, None)(idx)

    text = jax.jit(block).lower(
        arg((ML25M_USERS + 1,), jnp.int32), arg((rows, chunk), jnp.int32),
        arg((ML25M_THETA,), jnp.int32),
        arg((ML25M_M_S,), jnp.int32)).compile().as_text()
    assert re.search(rf"s32\[{rows},{chunk}\]\S* parameter\(1\)", text)
    sizes = _gathers_from(text, f"s32[{rows},{chunk}]")
    assert sizes and set(sizes) == {(1, chunk)}, sizes
    assert f"s32[{rows * chunk}]" not in text       # never flattened
    assert f"{ML25M_THETA},{ML25M_ITEMS}]" not in text
    assert f"{ML25M_ITEMS},{ML25M_THETA}]" not in text
    assert " while(" in text
    scattered = set(re.findall(r"= (\w+)\[(\d+)\]\S* scatter\(", text))
    assert ("s8", str(ML25M_THETA * ML25M_M_S)) in scattered, scattered
    assert not any(dtype == "f32" for dtype, _ in scattered), scattered


def _score_args(arg, codec, m, k):
    if codec == "dense":
        return ps.dense_topn, (arg((m, k)),)
    if codec == "int8":
        return ps.quant_topn, (arg((m, k), jnp.int8), arg((m, 1)))
    return ps.quant4_topn, (arg((m, (k + 1) // 2), jnp.uint8), arg((m, 1)),
                            k)


@pytest.mark.parametrize("codec", ["dense", "int8", "int4"])
@pytest.mark.parametrize("b", [8, 256])
@pytest.mark.parametrize("path", ["serve", "eval"])
def test_score_kernel_compiles_for_tpu(arg, codec, b, path):
    k = 25
    if path == "serve":
        # the serving engine's default block over the serving catalog
        m, mask = SERVE_M, None
        block = inspect.signature(ServingEngine).parameters["block_m"].default
    else:
        # periodic eval: the train-masked scorer at the derived block
        m, mask = M, arg((b, M))
        block = ops.fit_block_m(b, k, TOP_N)
    fn, wire = _score_args(arg, codec, m, k)
    _assert_kernel(fn, arg((b, k)), *wire, TOP_N, mask, block_m=block)


@pytest.mark.parametrize("b,k,want", [(8, 25, 4096), (256, 25, 1024),
                                      (512, 25, 512), (256, 512, 512)])
def test_fit_block_m_shrinks_with_batch_and_width(b, k, want):
    assert ops.fit_block_m(b, k, TOP_N) == want
