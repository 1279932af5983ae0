"""The reduction from a profiler trace to busy time, op times and gaps."""
import gzip
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench.harness import spec, trace

DATA = Path(__file__).resolve().parent / "data"
CUSTOM = 'custom_call_target="tpu_custom_call"'


def _profile(text):
    from jax.profiler import ProfileData

    return ProfileData.from_text_proto(text)


def _xspace(device_events, module_events, host_events):
    """A text XSpace: (name, start_us, dur_us) per event."""
    meta = {}

    def line(lid, name, events):
        rows = []
        for n, s, d in events:
            mid = meta.setdefault(n, len(meta) + 1)
            rows.append(f"events {{ metadata_id: {mid} offset_ps: "
                        f"{int(s * 1e6)} duration_ps: {int(d * 1e6)} }}")
        return (f'lines {{ id: {lid} name: "{name}" timestamp_ns: 0 '
                + " ".join(rows) + " }")

    def plane(pid, name, lines):
        md = " ".join(
            f'event_metadata {{ key: {i} value {{ id: {i} name: '
            f'"{n.replace(chr(34), chr(92) + chr(34))}" }} }}'
            for n, i in meta.items())
        return f'planes {{ id: {pid} name: "{name}" {lines} {md} }}'

    dev = plane(1, "/device:TPU:0",
                line(1, "XLA Ops", device_events)
                + " " + line(2, "XLA Modules", module_events))
    meta.clear()
    host = plane(2, "/host:CPU", line(1, "python", host_events))
    return dev + "\n" + host


def test_busy_is_the_union_of_leaf_ops_inside_the_window():
    text = _xspace(
        device_events=[
            ("%while.1 = (f32[4]) while()", 10, 50),          # parent
            (f"%fcf_grad.3 = f32[8,4] custom-call() {CUSTOM}", 10, 20),
            ("%fusion.7 = f32[8] fusion()", 25, 15),          # overlaps
            ("%fusion.8 = f32[8] fusion()", 45, 15),
            (f"%gather_rows.2 = f32[9,1,4] custom-call() {CUSTOM}", 80, 10),
        ],
        module_events=[("jit_scan_chunk(1)", 10, 50),
                       ("jit_eval(2)", 80, 10)],
        host_events=[("bench.window", 0, 100),
                     ("np.asarray(jax.Array)", 62, 16)])
    s = trace.reduce_profile(_profile(text))
    assert s.window_s == pytest.approx(100e-6)
    # leaves 10-40, 45-60, 80-90 (the while is not a leaf)
    assert s.busy_s == pytest.approx(55e-6)
    assert not [k for k in s.ops if k.startswith("while")]
    assert s.ops["fusion f32[8]"] == (2, pytest.approx(30e-6))
    assert trace.kernel_seconds(s, ["fcf_grad"]) == (1, pytest.approx(20e-6))
    assert [k.name for k in s.kernel_calls] == ["fcf_grad", "gather_rows"]
    assert trace.result_shape(s.kernel_calls[1]) == ("f32", [9, 1, 4])
    # 0-10 and 90-100 lie in no module and under no host span; 40-45 lies
    # inside the chunk program; 60-80 under the host's read-back
    assert s.gaps["host: untraced"] == pytest.approx(20e-6)
    assert s.gaps["in jit_scan_chunk"] == pytest.approx(5e-6)
    assert s.gaps["host: np.asarray(jax.Array)"] == pytest.approx(20e-6)
    assert s.top_gaps(1)[0][1] == pytest.approx(20e-6)


def test_window_defaults_to_the_device_ops():
    text = _xspace([("%fusion.1 = f32[2] fusion()", 5, 10),
                    ("%fusion.2 = f32[2] fusion()", 20, 5)], [], [])
    s = trace.reduce_profile(_profile(text))
    assert s.window_s == pytest.approx(20e-6)
    assert s.busy_s == pytest.approx(15e-6)


def test_a_trace_without_a_device_plane_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce_profile(_profile(
            'planes { id: 1 name: "/host:CPU" }'))


@pytest.fixture(scope="module")
def chip_trace():
    """150 ms of a lastfm.train.bts traced window, recorded on a TPU v5
    lite and cut with ``bench.tests.trace_fixture``."""
    with gzip.open(DATA / "lastfm_train_bts.xspace.txt.gz", "rt") as f:
        return trace.reduce_profile(_profile(f.read()))


def test_chip_trace_reduces(chip_trace):
    s = chip_trace
    assert s.window_s == pytest.approx(0.150, rel=1e-6)
    assert 0.0 < s.busy_s < s.window_s
    calls = {k.name for k in s.kernel_calls}
    assert {"fcf_grad", "gather_rows", "gather_quantize_rows",
            "scatter_set_rows"} <= calls
    # one fused gradient and one downlink gather+quantize per round
    n_grad, _ = trace.kernel_seconds(s, ["fcf_grad"])
    assert trace.kernel_seconds(s, ["gather_quantize_rows"])[0] == n_grad
    assert len(s.top_ops()) == 10 and len(s.top_gaps()) <= 10


@pytest.mark.parametrize("metric", [
    "fcf_grad_roofline", "row_kernels_roofline", "idle_share.train"])
def test_chip_trace_shares_are_percentages(chip_trace, metric):
    import json

    with open(spec.BENCH_DIR / "configs" / "fcf-lastfm.json") as f:
        config = json.load(f)
    cell = SimpleNamespace(config=config)
    ctx = SimpleNamespace(cell=cell, device_kind="TPU v5 lite",
                          summary=chip_trace, num_select=1763)
    value = spec.metric_reader(metric).read(ctx)
    assert value is not None and 0.0 < value < 100.0


def test_unknown_device_kind_is_an_error():
    from bench.harness.peaks import peaks_for

    with pytest.raises(ValueError):
        peaks_for("cpu")
