"""``list_gather_roofline.round``: the whole ``fl_gather`` scope's device time
against the cohort's list ids read once and its payload block written once,
at HBM bandwidth."""
from types import SimpleNamespace

import pytest

from bench.harness import counts, scopes, spec, trace
from bench.harness.peaks import PEAKS

METRIC = "list_gather_roofline.round"
KIND = "TPU v5 lite"
ROUNDS = 200


def _scopes(gather_s, other_s=0.004):
    """A traced window with one op under ``fl_gather`` taking ``gather_s``
    and one under ``fl_solve``, on one chip."""
    def op(name, stack, start, dur):
        return scopes.ScopedOp(trace.Op(name, name, start, start + dur),
                               stack, "jit_scan_chunk")

    ops = [op("scatter", ("jit(scan_chunk)", "while", "fl_gather"), 1.0,
              gather_s),
           op("custom-call", ("jit(scan_chunk)", "while", "fl_solve"),
              1.0 + gather_s, other_s)]
    return scopes.Scopes(window=(0.0, 10.0), ops=ops, spans=[], launches=[],
                         chips=1)


def _ctx(cell_name, s, rounds=ROUNDS):
    cell = spec.find_cell(cell_name)
    tr = cell.traffic["training"]
    m_s = counts.num_select(cell.config["data"]["num_items"], tr["strategy"],
                            tr["keep_fraction"])
    return SimpleNamespace(cell=cell, scopes=s, traced_rounds=rounds,
                           num_select=m_s, device_kind=KIND)


def test_reads_the_list_bound_over_the_gather_scope():
    # MovieLens-25M: Theta=1000 users of mean train degree 25,000,095 x 0.8
    # / 162,541 ids each, two int32 offsets per user, the (1000, 6,242)
    # float32 block written once
    d = 25_000_095 * 0.8 / 162_541
    nbytes = 4 * 1000 * d + 8 * 1000 + 4 * 1000 * 6_242
    gather_s = 0.6                               # 3 ms a round over 200
    got = spec.metric_reader(METRIC).read(_ctx("ml25m.train.bts",
                                               _scopes(gather_s)))
    bound_s = nbytes / PEAKS[KIND]["hbm_bytes_per_s"]
    assert got == pytest.approx(100.0 * bound_s / (gather_s / ROUNDS),
                                rel=1e-12)
    assert 1.0 < got < 2.0


def test_is_the_gather_ms_reading_against_the_bound():
    s = _scopes(0.25)
    ms = spec.metric_reader("gather_ms.round").read(
        SimpleNamespace(scopes=s, traced_rounds=ROUNDS))
    assert ms == pytest.approx(1e3 * 0.25 / ROUNDS, rel=1e-12)
    reader = spec.metric_reader(METRIC)
    ctx = _ctx("ml25m.train.bts", s)
    nbytes = reader.list_gather_bytes(ctx.cell.config, ctx.num_select)
    assert reader.read(ctx) == pytest.approx(
        100.0 * nbytes / PEAKS[KIND]["hbm_bytes_per_s"] / (ms / 1e3),
        rel=1e-12)


def test_finds_nothing_without_the_gather_scope():
    s = _scopes(0.25)
    s = s._replace(ops=[o for o in s.ops if "fl_gather" not in o.stack])
    assert spec.metric_reader(METRIC).read(_ctx("ml25m.train.bts", s)) \
        is None


@pytest.mark.parametrize("ctx", [
    SimpleNamespace(),
    SimpleNamespace(cell=SimpleNamespace(name="no.such.cell"),
                    traced_rounds=25),
    SimpleNamespace(cell=SimpleNamespace(name="no.such.cell"),
                    traced_rounds=25, num_select=6_242, device_kind=KIND,
                    summary=SimpleNamespace(chips=1))],
    ids=["empty", "untraced", "trace_gone"])
def test_finds_nothing_without_a_trace(ctx):
    assert spec.metric_reader(METRIC).read(ctx) is None


def test_only_the_lists_cell_reads_it():
    bench = spec.load_benchmark()
    (entry,) = [m for m in bench["per_layer"] if m["name"] == METRIC]
    assert entry["workloads"] == ["ml25m.train.bts"]
    assert entry["moves"] == "rounds_per_s"
    (dense,) = [m for m in bench["per_layer"]
                if m["name"] == "gather_roofline.round"]
    assert "ml25m.train.bts" not in dense["workloads"]
