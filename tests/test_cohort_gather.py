"""The cohort block ``X[cohort][:, selected]``: every engine's gather is
bit-equal to the one-step element index, and none compiles to one.

``_cohort_block`` gathers the cohort's user rows whole, then the payload
columns as rows of that slab's transpose. The single-device and async
engines reach it through ``_blocked_cohort_x``, the shard engine through
``_local_cohort_x``, the vmapped seed sweep directly.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.federated.simulation import (_blocked_cohort_x, _cohort_block,
                                        _local_cohort_x, _pad_cohort)

USERS, ITEMS = 37, 211


def _train(seed=0):
    """Sparse non-negative interactions with distinct nonzero values."""
    rng = np.random.default_rng(seed)
    x = rng.random((USERS, ITEMS), dtype=np.float32)
    x[rng.random((USERS, ITEMS)) < 0.7] = 0.0
    return x


def _element_block(train, ids, idx):
    """The one-step element gather the helper replaces."""
    return np.array(jnp.asarray(train)[jnp.asarray(ids)[:, None],
                                        jnp.asarray(idx)[None, :]])


def _idx(kind, rng):
    if kind == "sorted":
        return np.sort(rng.choice(ITEMS, 23, replace=False))
    if kind == "unsorted":
        return rng.choice(ITEMS, 23, replace=False)
    if kind == "all_columns":
        return np.arange(ITEMS)
    if kind == "all_columns_permuted":
        return rng.permutation(ITEMS)
    raise ValueError(kind)


IDX_KINDS = ["sorted", "unsorted", "all_columns", "all_columns_permuted"]


@pytest.mark.parametrize("idx_kind", IDX_KINDS)
@pytest.mark.parametrize("ids_kind", ["distinct", "repeated"])
def test_helper_matches_element_gather(idx_kind, ids_kind):
    rng = np.random.default_rng(1)
    train = _train()
    ids = rng.choice(USERS, 9, replace=False)
    if ids_kind == "repeated":                     # pad ids reuse user 0
        ids = np.concatenate([ids, [0, 0, 0]])
    idx = _idx(idx_kind, rng)
    got = jax.jit(_cohort_block)(jnp.asarray(train),
                                 jnp.asarray(ids, jnp.int32),
                                 jnp.asarray(idx, jnp.int32))
    assert got.shape == (len(ids), len(idx))
    assert np.array_equal(np.asarray(got), _element_block(train, ids, idx))


def _cohort_case(num_users, shards, survivors, idx_kind, seed=2):
    """A padded cohort, its per-slot keep vector (or None), the payload
    columns, and the block every closure must return."""
    rng = np.random.default_rng(seed)
    train = _train(seed)
    cohort = jnp.asarray(rng.choice(USERS, num_users, replace=False),
                         jnp.int32)
    ids = _pad_cohort(cohort, shards)
    keep = None
    if survivors:
        keep = (rng.random(ids.shape[0]) < 0.6).astype(np.float32)
    idx = _idx(idx_kind, rng)
    want = _element_block(train, np.asarray(ids), idx)
    want[num_users:] = 0.0                         # pad rows
    if keep is not None:
        want = want * keep[:, None]
        keep = jnp.asarray(keep)
    return jnp.asarray(train), ids, keep, jnp.asarray(idx, jnp.int32), want


# (num_users, shards, survivors): unpadded and padded cohorts, one block
# and C > 1 blocks, with and without the fault layer's survivors mask
COHORTS = [(12, 1, False), (12, 3, False), (10, 4, False), (10, 4, True),
           (12, 3, True), (7, 1, True)]


@pytest.mark.parametrize("idx_kind", ["unsorted", "all_columns"])
@pytest.mark.parametrize("num_users,shards,survivors", COHORTS)
def test_blocked_cohort_x_matches_element_gather(num_users, shards,
                                                 survivors, idx_kind):
    train, ids, keep, idx, want = _cohort_case(num_users, shards, survivors,
                                               idx_kind)
    fn = _blocked_cohort_x(train, ids, shards, num_users, survivors=keep)
    got = jax.jit(fn)(idx)
    total = ids.shape[0]
    assert got.shape == (shards, total // shards, idx.shape[0])
    assert np.array_equal(np.asarray(got).reshape(total, -1), want)


@pytest.mark.parametrize("idx_kind", ["unsorted", "all_columns"])
@pytest.mark.parametrize("num_users,shards,survivors", COHORTS)
def test_local_cohort_x_matches_element_gather(num_users, shards,
                                               survivors, idx_kind):
    """Each device's block, stacked in device order, is the whole padded
    cohort's block, zeroed as on one device."""
    train, ids, keep, idx, want = _cohort_case(num_users, shards, survivors,
                                               idx_kind)
    b = ids.shape[0] // shards
    blocks = []
    for d in range(shards):
        fn = _local_cohort_x(ids[d * b:(d + 1) * b], jnp.int32(d), train,
                             shards, num_users, survivors=keep)
        got = jax.jit(fn)(idx)
        assert got.shape == (1, b, idx.shape[0])
        blocks.append(np.asarray(got[0]))
    assert np.array_equal(np.concatenate(blocks), want)


@pytest.mark.parametrize("shared_data", [False, True])
def test_vmapped_seed_sweep_block(shared_data):
    """The seed sweep's closure under ``jax.vmap``: per-seed data (or one
    shared matrix) and per-seed cohorts and payload subsets."""
    seeds = 3
    rng = np.random.default_rng(4)
    trains = np.stack([_train(s) for s in range(seeds)])
    cohorts = np.stack([rng.choice(USERS, 11, replace=False)
                        for _ in range(seeds)]).astype(np.int32)
    idxs = np.stack([rng.permutation(ITEMS)[:29]
                     for _ in range(seeds)]).astype(np.int32)
    if shared_data:
        fn = jax.vmap(_cohort_block, in_axes=(None, 0, 0))
        got = jax.jit(fn)(jnp.asarray(trains[0]), cohorts, idxs)
        data = [trains[0]] * seeds
    else:
        got = jax.jit(jax.vmap(_cohort_block))(jnp.asarray(trains), cohorts,
                                               idxs)
        data = list(trains)
    for s in range(seeds):
        assert np.array_equal(np.asarray(got[s]),
                              _element_block(data[s], cohorts[s], idxs[s]))


_GATHER = re.compile(r"=\s*(\S+)\s+gather\(.*?slice_sizes=\{([0-9,]+)\}")


def _gathers(hlo_text):
    """(result type, slice sizes) of every gather in compiled HLO text."""
    return [(m.group(1), tuple(int(v) for v in m.group(2).split(",")))
            for m in _GATHER.finditer(hlo_text)]


@pytest.mark.parametrize("num_users,shards,survivors",
                         [(12, 1, False), (10, 4, True)])
def test_blocked_cohort_x_compiles_to_slice_gathers(num_users, shards,
                                                    survivors):
    """No gather of the compiled closure takes single elements: the row
    stage moves whole rows of the interaction matrix, the column stage
    whole rows of the slab's transpose. The matrix is a constant and the
    cohort an argument, as in the chunk program."""
    train, ids, keep, idx, _ = _cohort_case(num_users, shards, survivors,
                                            "unsorted")

    def fn(ids, idx):
        return _blocked_cohort_x(train, ids, shards, num_users,
                                 survivors=keep)(idx)

    gathers = _gathers(jax.jit(fn).lower(ids, idx).compile().as_text())
    assert gathers, "the closure compiled to no gather at all"
    for result, sizes in gathers:
        assert any(s > 1 for s in sizes), (result, sizes)
    assert any(ITEMS in sizes for _, sizes in gathers), gathers


def test_element_gather_is_what_the_guard_refuses():
    """The guard's pattern finds the element gather it exists to keep out."""
    train, ids, _, idx, _ = _cohort_case(12, 1, False, "unsorted")
    text = jax.jit(lambda u, i: train[u[:, None], i[None, :]]).lower(
        ids, idx).compile().as_text()
    assert any(all(s == 1 for s in sizes) for _, sizes in _gathers(text))
