"""``gather_roofline.round``: the whole ``fl_gather`` scope's device time
against the cohort's user rows read whole and its payload block written
once, at HBM bandwidth."""
import gzip
from types import SimpleNamespace

import pytest

from bench.harness import counts, spec
from bench.harness.peaks import PEAKS
from bench.tests.test_bench_scopes import (DATA, FIXTURE_ROUNDS, _read,
                                           _scopes)

METRIC = "gather_roofline.round"
KIND = "TPU v5 lite"


def _ctx(cell_name, s, rounds=FIXTURE_ROUNDS, **extra):
    cell = spec.find_cell(cell_name)
    tr = cell.traffic["training"]
    m_s = counts.num_select(cell.config["data"]["num_items"], tr["strategy"],
                            tr["keep_fraction"])
    return SimpleNamespace(cell=cell, scopes=s, traced_rounds=rounds,
                           num_select=m_s, device_kind=KIND, **extra)


@pytest.fixture(scope="module")
def chip_scopes():
    """A lastfm.train.bts chunk boundary, recorded on a TPU v5 lite while
    the cohort gather still took single elements."""
    with gzip.open(DATA / "lastfm_train_bts.scopes.txt.gz", "rt") as f:
        return _scopes(f.read())


def test_reads_the_row_bound_over_the_gather_scope(chip_scopes):
    # lastfm: Theta=100 rows of M=17,632 read, the (100, 1,763) block
    # written, float32
    nbytes = 4 * 100 * (17_632 + 1_763)
    bound_ms = 1e3 * nbytes / PEAKS[KIND]["hbm_bytes_per_s"]
    gather_ms = _read("gather_ms.round", chip_scopes)
    got = spec.metric_reader(METRIC).read(_ctx("lastfm.train.bts",
                                               chip_scopes))
    assert got == pytest.approx(100.0 * bound_ms / gather_ms, rel=1e-12)
    # the element gather of the fixture's program: about 0.4%
    assert 0.3 < got < 0.5


@pytest.mark.parametrize("cell,rows_bytes", [
    ("lastfm.train.bts", 4 * 100 * (17_632 + 1_763)),
    ("mind.train.bts", 4 * 500 * (6_923 + 692))])
def test_scales_with_each_cells_cohort_rows(chip_scopes, cell, rows_bytes):
    """The same gather time read against each cell's bytes: the share is
    proportional to Theta x (M + M_s)."""
    lastfm = spec.metric_reader(METRIC).read(_ctx("lastfm.train.bts",
                                                  chip_scopes))
    got = spec.metric_reader(METRIC).read(_ctx(cell, chip_scopes))
    assert got == pytest.approx(
        lastfm * rows_bytes / (4 * 100 * (17_632 + 1_763)), rel=1e-12)


def test_finds_nothing_in_a_trace_without_names():
    with gzip.open(DATA / "lastfm_train_bts.xspace.txt.gz", "rt") as f:
        s = _scopes(f.read())
    reader = spec.metric_reader(METRIC)
    assert reader.read(_ctx("lastfm.train.bts", s, rounds=6)) is None


@pytest.mark.parametrize("ctx", [
    SimpleNamespace(),
    SimpleNamespace(cell=SimpleNamespace(name="no.such.cell"),
                    traced_rounds=25),
    SimpleNamespace(cell=SimpleNamespace(name="no.such.cell"),
                    traced_rounds=25, num_select=1_763, device_kind=KIND,
                    summary=SimpleNamespace(chips=1))],
    ids=["empty", "untraced", "trace_gone"])
def test_finds_nothing_without_a_trace(ctx):
    assert spec.metric_reader(METRIC).read(ctx) is None
