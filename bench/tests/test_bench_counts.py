"""The algorithm's operations and bytes at the configurations' shapes."""
import json
from pathlib import Path

import pytest

from bench.harness import counts

BENCH = Path(__file__).resolve().parents[1]


def _config(name):
    with open(BENCH / "configs" / f"{name}.json") as f:
        return json.load(f)


@pytest.mark.parametrize("name, strategy, keep, m_s", [
    ("fcf-lastfm", "bts", 0.1, 1763),
    ("fcf-mind", "bts", 0.1, 692),
    ("fcf-lastfm", "full", 1.0, 17632),
])
def test_payload_rows_at_config_shapes(name, strategy, keep, m_s):
    m = _config(name)["data"]["num_items"]
    assert counts.num_select(m, strategy, keep) == m_s


def test_solve_ops_lastfm_by_hand():
    theta, m_s, k = 100, 1763, 25
    tri = 325
    want = (2 * m_s * k * k + m_s * tri + 2 * theta * m_s * tri
            + 2 * theta * m_s * k + theta * k ** 3 / 3 + 2 * theta * k * k)
    assert counts.solve_ops(theta, m_s, k) == pytest.approx(want)
    # the dense confidence correction is most of it
    assert 2 * theta * m_s * tri / want > 0.9


def test_round_ops_at_config_shapes():
    lastfm = counts.round_ops(17632, 1763, 100, 25, "bts")
    mind = counts.round_ops(6923, 692, 500, 25, "bts")
    full = counts.round_ops(17632, 17632, 100, 25, "full")
    assert 1.3e8 < lastfm < 1.6e8        # ~0.15 GFLOP a round
    assert 2.5e8 < mind < 3.0e8          # 5x the cohort, 0.4x the rows
    assert full == pytest.approx(
        counts.solve_ops(100, 17632, 25) + counts.fcf_grad_ops(100, 17632, 25)
        + 28.0 * 17632 * 25)


def test_fcf_grad_cost_lastfm():
    theta, m_s, k = 100, 1763, 25
    assert counts.fcf_grad_ops(theta, m_s, k) == pytest.approx(
        4 * theta * m_s * k + 3 * theta * m_s + 3 * m_s * k)
    assert counts.fcf_grad_bytes(theta, m_s, k) == 4 * (
        theta * k + 2 * m_s * k + theta * m_s)


@pytest.mark.parametrize("kernel, per_row", [
    ("gather_rows", 200), ("scatter_set_rows", 200),
    ("gather_quantize_rows", 129), ("dequant_scatter_set_rows", 129),
])
def test_row_kernel_bytes_count_rows_not_tiles(kernel, per_row):
    # K=25 f32 rows are 100 bytes; a (1, 128)-tiled row would be 512
    assert counts.row_kernel_bytes(kernel, 1763, 25) == 1763 * per_row


def test_score_cost_int8_bucket():
    b, m, k, n = 256, 17632, 25, 10
    assert counts.score_ops(b, m, k) == 2 * b * m * k + 2 * m * k
    assert counts.score_bytes(b, m, k, n) == (
        m * k + 4 * m + 4 * b * m + 4 * b * k + 8 * b * n)
