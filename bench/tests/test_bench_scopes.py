"""The readers of the program's own names in a trace: phase scopes on the
device ops, spans and program launches on the host."""
import bisect
import gzip
import math
import re
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench.harness import scopes, spec, trace

DATA = Path(__file__).resolve().parent / "data"
ROUND_METRICS = {"gather_ms.round": "fl_gather", "solve_ms.round": "fl_solve",
                 "select_ms.round": "fl_select",
                 "commit_ms.round": "fl_commit"}
NEW_METRICS = list(ROUND_METRICS) + [
    "scoped_share.round", "eval_ms.train", "publish_programs.train"]
FIXTURE_ROUNDS = 25     # one chunk boundary of lastfm.train.bts


def _serialized(text):
    from jax.profiler import ProfileData

    return ProfileData.text_proto_to_serialized_xspace(text)


def _scopes(text):
    from jax.profiler import ProfileData

    xspace = _serialized(text)
    return scopes.reduce(ProfileData.from_serialized_xspace(xspace),
                         scopes.name_stacks(xspace))


def _read(metric, s, rounds=FIXTURE_ROUNDS):
    ctx = SimpleNamespace(scopes=s, traced_rounds=rounds)
    return spec.metric_reader(metric).read(ctx)


_STR = r'"((?:[^"\\]|\\.)*)"'
_META = re.compile(r"event_metadata \{ key: (\d+) value \{ id: \d+ name: "
                   + _STR + r"(?: stats \{ metadata_id: 1 str_value: "
                   + _STR + r" \})? \} \}")
_LINE = re.compile(r'  lines \{\n    id: \d+\n    name: ' + _STR)
_EVENT = re.compile(r"events \{ metadata_id: (\d+) offset_ps: (\d+) "
                    r"duration_ps: (\d+) \}")


def _by_hand(text):
    """{plane: {line: [(name, tf_op, start_s, end_s)]}}, read from the text
    with regular expressions rather than through the harness."""
    out = {}
    for block in text.split("planes {\n")[1:]:
        plane = re.search(r"name: " + _STR, block).group(1)
        meta = {m.group(1): (m.group(2), m.group(3) or "")
                for m in _META.finditer(block)}
        lines = {}
        heads = list(_LINE.finditer(block))
        for i, head in enumerate(heads):
            end = heads[i + 1].start() if i + 1 < len(heads) else len(block)
            lines[head.group(1)] = [
                meta[e.group(1)] + (int(e.group(2)) * 1e-12,
                                    (int(e.group(2)) + int(e.group(3)))
                                    * 1e-12)
                for e in _EVENT.finditer(block, head.end(), end)]
        out[plane] = lines
    return out


@pytest.fixture(scope="module")
def fixture_text():
    """One chunk boundary of a lastfm.train.bts traced window, recorded on a
    TPU v5 lite with the program's scopes and spans, and cut with
    ``bench.tests.scope_fixture``."""
    with gzip.open(DATA / "lastfm_train_bts.scopes.txt.gz", "rt") as f:
        return f.read()


@pytest.fixture(scope="module")
def chip_scopes(fixture_text):
    return _scopes(fixture_text)


@pytest.fixture(scope="module")
def hand(fixture_text):
    planes = _by_hand(fixture_text)
    dev, host = planes["/device:TPU:0"], planes["/host:CPU"]
    ((_, _, lo, hi),) = host["bench"]
    inside = sorted((s, e, n, st) for n, st, s, e in dev["XLA Ops"]
                    if e > lo and s < hi)
    starts = [x[0] for x in inside]
    # leaf ops: those in which no shorter op starts and ends (the scan's
    # while spans its body; a kernel can span a shorter one)
    ops = [(n, st, max(s, lo), min(e, hi)) for s, e, n, st in inside
           if not any(e2 <= e and e2 - s2 < e - s for s2, e2, _, _ in
                      inside[bisect.bisect_left(starts, s):
                             bisect.bisect_left(starts, e)])]
    return SimpleNamespace(ops=ops, modules=dev["XLA Modules"],
                           host=[x for line in host.values() for x in line],
                           window=(lo, hi))


@pytest.mark.parametrize("metric", sorted(ROUND_METRICS))
def test_round_phase_reader_matches_the_sum_by_hand(chip_scopes, hand,
                                                    metric):
    scope = ROUND_METRICS[metric]
    secs = sum(e - s for _, st, s, e in hand.ops if f"/{scope}/" in st)
    assert secs > 0
    assert _read(metric, chip_scopes) == pytest.approx(
        1e3 * secs / FIXTURE_ROUNDS, rel=1e-9)


def test_scoped_share_matches_the_sum_by_hand(chip_scopes, hand):
    chunk = [(s, e) for n, _, s, e in hand.modules
             if n.startswith("jit_scan_chunk(")]
    ops = [(st, s, e) for _, st, s, e in hand.ops
           if any(a <= s <= b for a, b in chunk)]
    named = sum(e - s for st, s, e in ops if "/fl_" in st)
    share = _read("scoped_share.round", chip_scopes)
    assert share == pytest.approx(100 * named / sum(e - s for _, s, e in ops),
                                  rel=1e-9)
    assert 95.0 <= share < 100.0


def test_eval_reader_adds_the_runs_that_eval_launched(chip_scopes, hand):
    (span,) = [(s, e) for n, _, s, e in hand.host if n == "eval"]
    lo, hi = hand.window
    launched = ("jit_solve_user_factors(", "jit_transpose(", "jit_matmul(",
                "jit_ranked_metrics(")
    runs = [e - s for n, _, s, e in hand.modules
            if n.startswith(launched) and lo <= s <= hi]
    assert len(runs) == 4
    # the runs wait for the chunk program: they start long after the eval
    # span opens, so no overlap test with the span could find them
    assert all(s > span[0] + 0.09 for n, _, s, e in hand.modules
               if n.startswith(launched) and lo <= s <= hi)
    assert _read("eval_ms.train", chip_scopes) == pytest.approx(
        1e3 * sum(runs), rel=1e-9)


def test_publish_reader_counts_the_launches_inside_publish(chip_scopes,
                                                           hand):
    (span,) = [(s, e) for n, _, s, e in hand.host if n == "publish"]
    marks = [s for n, _, s, _ in hand.host
             if n == scopes.LAUNCH and span[0] <= s <= span[1]]
    assert len(marks) == 10     # encode() dispatched op by op
    assert _read("publish_programs.train", chip_scopes) == len(marks)
    (encode,) = chip_scopes.named("publish.encode")
    assert encode.launches[0] == "abs" and len(encode.runs) == 10
    assert chip_scopes.named("publish.install")[0].launches == []


def test_gather_scope_agrees_with_the_shape_heuristic(chip_scopes):
    # the block by its shape alone: every leaf outside the kernels whose
    # result holds Theta x M_s values (100 x 1763 on lastfm)
    block = [o for o in chip_scopes.ops
             if "tpu_custom_call" not in o.op.text
             and math.prod(trace.result_shape(o.op)[1] or [0]) == 176300]
    by_shape = 1e3 * chip_scopes.seconds(block) / FIXTURE_ROUNDS
    assert _read("gather_ms.round", chip_scopes) == pytest.approx(
        by_shape, rel=0.10)


def test_chunk_run_starts_inside_its_span(chip_scopes):
    (chunk,) = chip_scopes.named("train_chunk")
    (run,) = chunk.runs
    assert run.module == "jit_scan_chunk" and chunk.start < run.start


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_readers_find_nothing_in_a_trace_without_names(metric):
    """The trace of a program without scopes or spans (the recorded fixture
    of ``test_bench_trace``) reads None, never 0."""
    with gzip.open(DATA / "lastfm_train_bts.xspace.txt.gz", "rt") as f:
        s = _scopes(f.read())
    assert not s.scoped and not s.spans
    assert _read(metric, s, rounds=6) is None


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_readers_find_nothing_without_a_trace(metric):
    """An untraced run (no summary) and a traced one whose trace is gone
    both read None."""
    cell = SimpleNamespace(name="no.such.cell")
    for ctx in (SimpleNamespace(cell=cell, traced_rounds=25),
                SimpleNamespace(cell=cell, traced_rounds=25,
                                summary=SimpleNamespace(chips=1))):
        assert spec.metric_reader(metric).read(ctx) is None


def _plane(pid, name, lines, meta, stats=()):
    """A text XPlane: ``lines`` as (name, [(event, start_us, dur_us)]),
    ``meta`` event name -> tf_op, ``stats`` the stat metadata names."""
    ids = {}
    rows = []
    for lid, (lname, events) in enumerate(lines, 1):
        evs = []
        for n, s, d in events:
            mid = ids.setdefault(n, len(ids) + 1)
            evs.append(f"events {{ metadata_id: {mid} offset_ps: "
                       f"{int(s * 1e6)} duration_ps: {int(d * 1e6)} }}")
        rows.append(f'lines {{ id: {lid} name: "{lname}" timestamp_ns: 0 '
                    + " ".join(evs) + " }")
    for n, mid in ids.items():
        stat = ""
        if n in meta:
            stat = (f" stats {{ metadata_id: 2 ref_value: 3 }}"
                    if meta[n] == "ref" else
                    f' stats {{ metadata_id: 2 str_value: "{meta[n]}" }}')
        rows.append(f'event_metadata {{ key: {mid} value {{ id: {mid} '
                    f'name: "{n}"{stat} }} }}')
    for sid, sname in enumerate(stats, 1):
        rows.append(f'stat_metadata {{ key: {sid} value {{ id: {sid} '
                    f'name: "{sname}" }} }}')
    return f'planes {{ id: {pid} name: "{name}" ' + " ".join(rows) + " }"


def test_launches_tie_to_runs_by_order_not_by_time():
    """Each launch takes the next run of the program it names, even where
    the device clock puts the run before the launch; a launch that names
    no program takes a run left over and is named by its module."""
    gather = "jit(scan_chunk)/while/body/fl_gather/gather:"
    dev = _plane(1, "/device:TPU:0", [
        ("XLA Ops", [("%fusion.1 = f32[8] fusion()", 12, 6),
                     ("%fusion.2 = f32[8] fusion()", 20, 4),
                     ("%copy.3 = f32[8] copy()", 24, 1)]),
        ("XLA Modules", [("jit_scan_chunk(7)", 11, 15),
                         ("jit_abs(8)", 38, 1),     # before its launch
                         ("jit_abs(8)", 45, 1),
                         ("jit_clip(9)", 50, 2)])],
        {"%fusion.1 = f32[8] fusion()": gather,
         "%fusion.2 = f32[8] fusion()": "ref"},
        stats=("other", "tf_op", "jit(scan_chunk)/while/body/fl_solve/dot:"))
    host = _plane(2, "/host:CPU", [
        ("python", [("bench.window", 0, 100), ("train_chunk", 5, 8),
                    ("PjitFunction(scan_chunk)", 6, 4),
                    (scopes.LAUNCH, 8, 0.1),
                    ("publish", 30, 25),
                    ("PjitFunction(abs)", 39, 2), (scopes.LAUNCH, 40, 0.1),
                    ("PjitFunction(abs)", 43, 2), (scopes.LAUNCH, 44, 0.1),
                    (scopes.LAUNCH, 48, 0.1)])], {})
    s = _scopes(dev + "\n" + host)
    (chunk,) = s.named("train_chunk")
    assert [r.module for r in chunk.runs] == ["jit_scan_chunk"]
    (pub,) = s.named("publish")
    assert pub.launches == ["abs", "abs", "clip"]
    assert [r.start for r in pub.runs] == pytest.approx([38e-6, 45e-6, 50e-6])
    # a str_value stack and a ref_value one; the copy carries none
    assert [o.stack[-2] if o.stack else None for o in s.ops] == [
        "fl_gather", "fl_solve", None]
    assert all(o.module == "jit_scan_chunk" for o in s.ops)
    assert _read("scoped_share.round", s) == pytest.approx(100 * 10 / 11)
    assert _read("gather_ms.round", s, rounds=2) == pytest.approx(3e-3)
    assert _read("publish_programs.train", s) == 3
    assert _read("eval_ms.train", s) is None


def test_name_stacks_follow_the_metadata_id_not_the_op_text():
    """Two programs may hold an op of the same HLO text: each event keeps
    the name stack of its own metadata."""
    text = "%copy.1 = f32[8] copy()"
    dev = (
        'planes { id: 1 name: "/device:TPU:0" '
        'lines { id: 1 name: "XLA Ops" timestamp_ns: 0 '
        'events { metadata_id: 1 offset_ps: 12000000 duration_ps: 2000000 } '
        'events { metadata_id: 2 offset_ps: 32000000 duration_ps: 3000000 } }'
        ' lines { id: 2 name: "XLA Modules" timestamp_ns: 0 '
        'events { metadata_id: 3 offset_ps: 11000000 duration_ps: 5000000 } '
        'events { metadata_id: 4 offset_ps: 31000000 duration_ps: 5000000 }'
        ' } '
        f'event_metadata {{ key: 1 value {{ id: 1 name: "{text}" stats {{ '
        'metadata_id: 1 str_value: "jit(scan_chunk)/fl_commit/copy:" } } } '
        f'event_metadata {{ key: 2 value {{ id: 2 name: "{text}" stats {{ '
        'metadata_id: 1 str_value: "jit(other)/copy:" } } } '
        'event_metadata { key: 3 value { id: 3 name: "jit_scan_chunk(1)" } } '
        'event_metadata { key: 4 value { id: 4 name: "jit_other(2)" } } '
        'stat_metadata { key: 1 value { id: 1 name: "tf_op" } } }')
    host = _plane(2, "/host:CPU", [("python", [("bench.window", 0, 100)])],
                  {})
    s = _scopes(dev + "\n" + host)
    assert [(o.module, o.stack) for o in s.ops] == [
        ("jit_scan_chunk", ("jit(scan_chunk)", "fl_commit", "copy")),
        ("jit_other", ("jit(other)", "copy"))]
    assert _read("commit_ms.round", s, rounds=1) == pytest.approx(2e-3)


def test_launches_tie_to_runs_on_each_chip():
    """A launch of a program that runs on two chips takes one run on each;
    a span's device time is per chip."""
    def chip(i, shift):
        return _plane(i + 1, f"/device:TPU:{i}", [
            ("XLA Ops", [("%fusion.1 = f32[8] fusion()", 12 + shift, 6)]),
            ("XLA Modules", [("jit_scan_chunk(7)", 11 + shift, 15),
                             ("jit_abs(8)", 38 + shift, 2),
                             ("jit_abs(8)", 45 + shift, 4)])],
            {"%fusion.1 = f32[8] fusion()":
             "jit(scan_chunk)/while/body/fl_gather/gather:"},
            stats=("other", "tf_op"))
    host = _plane(3, "/host:CPU", [
        ("python", [("bench.window", 0, 100), ("train_chunk", 5, 8),
                    ("PjitFunction(scan_chunk)", 6, 4),
                    (scopes.LAUNCH, 8, 0.1),
                    ("eval", 30, 25),
                    ("PjitFunction(abs)", 39, 2), (scopes.LAUNCH, 40, 0.1),
                    ("PjitFunction(abs)", 43, 2), (scopes.LAUNCH, 44, 0.1)])],
        {})
    from jax.profiler import ProfileData

    xspace = _serialized("\n".join([chip(0, 0), chip(1, 1), host]))
    s = scopes.reduce(ProfileData.from_serialized_xspace(xspace),
                      scopes.name_stacks(xspace), chips=2)
    assert s.chips == 2
    assert [len(x.runs) for x in s.launches] == [2, 2, 2]
    (ev,) = s.named("eval")
    assert sorted(r.start for r in ev.runs) == pytest.approx(
        [38e-6, 39e-6, 45e-6, 46e-6])
    assert _read("eval_ms.train", s) == pytest.approx(6e-3)
    assert _read("gather_ms.round", s, rounds=1) == pytest.approx(6e-3)
