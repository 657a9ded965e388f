"""Bench trajectory tool (ISSUE 14 satellite): the r1-rN trend,
box-normalized and machine-gated."""

import json

import pytest

from limitador_tpu.tools.bench_trend import (
    collect_rounds,
    main,
    normalized_value,
    regressions,
    render_markdown,
    trend_table,
)


def _capture(path, n, metric_rows, headline=None, rc=0):
    tail = "\n".join(
        ["some log noise", *(json.dumps(r) for r in metric_rows),
         "more noise"]
    )
    path.write_text(json.dumps({
        "n": n, "cmd": "python bench.py", "rc": rc, "tail": tail,
        "parsed": headline or (metric_rows[0] if metric_rows else None),
    }))


def _row(metric, value, cal=None, unit="decisions/s", **extra):
    row = {"metric": metric, "value": value, "unit": unit, **extra}
    if cal is not None:
        row["box_calibration_score"] = cal
    return row


def test_normalized_value_rates_and_latencies():
    assert normalized_value(_row("engine_decisions_per_sec", 1e6,
                                 cal=20.0)) == 5e4
    # latency: a slower box LOWERS the score and RAISES the ms — the
    # product is the box-independent figure
    assert normalized_value(_row("serving_p99_ms", 2.0, cal=20.0,
                                 unit="ms")) == 40.0
    assert normalized_value(_row("engine_decisions_per_sec", 1e6)) is None


def test_trend_reads_parsed_and_tail_rows(tmp_path):
    _capture(tmp_path / "BENCH_r01.json", 1,
             [_row("engine_decisions_per_sec", 1e6, cal=20.0)])
    _capture(tmp_path / "BENCH_r02.json", 2,
             [_row("engine_decisions_per_sec", 2.2e6, cal=40.0),
              _row("serving_p99_ms", 1.5, cal=40.0, unit="ms")])
    rounds = collect_rounds("BENCH_r*.json", tmp_path)
    assert [r["round"] for r in rounds] == [1, 2]
    table = trend_table(rounds)
    assert len(table["engine_decisions_per_sec"]) == 2
    # r2's raw rate is 2.2x r1 but on a 2x-faster box: normalized
    # 5e4 -> 5.5e4, a ~10% true gain
    series = table["engine_decisions_per_sec"]
    assert series[0]["normalized"] == 5e4
    assert series[1]["normalized"] == pytest.approx(5.5e4)
    assert not regressions(table, tolerance=0.5)
    md = render_markdown(table, [])
    assert "engine_decisions_per_sec" in md
    assert "No normalized regression" in md


def test_regression_gate_fires_on_normalized_drop(tmp_path):
    _capture(tmp_path / "BENCH_r01.json", 1,
             [_row("engine_decisions_per_sec", 1e6, cal=20.0)])
    # r2: raw rate UP 1.5x but the box is 4x faster — normalized the
    # round lost 62% of throughput: a real regression hidden by hardware
    _capture(tmp_path / "BENCH_r02.json", 2,
             [_row("engine_decisions_per_sec", 1.5e6, cal=80.0)])
    table = trend_table(collect_rounds("BENCH_r*.json", tmp_path))
    regs = regressions(table, tolerance=0.5)
    assert len(regs) == 1
    assert regs[0]["metric"] == "engine_decisions_per_sec"
    assert regs[0]["retained_share"] == pytest.approx(0.375)
    # within tolerance -> quiet
    assert not regressions(table, tolerance=0.7)


def test_gate_ignores_backend_changes_and_uncalibrated_rows(tmp_path):
    # r1 device-backed, r2 CPU fallback: a backend change, not a
    # regression — and r0-style rows without the score never gate
    _capture(tmp_path / "BENCH_r01.json", 1,
             [_row("engine_decisions_per_sec", 1e8,
                   device_backed=True)])
    _capture(tmp_path / "BENCH_r02.json", 2,
             [_row("engine_decisions_per_sec", 1e6, cal=20.0,
                   device_backed=True)])
    _capture(tmp_path / "BENCH_r03.json", 3,
             [_row("engine_decisions_per_sec", 0.9e6, cal=20.0,
                   device_backed=False)])
    table = trend_table(collect_rounds("BENCH_r*.json", tmp_path))
    assert not regressions(table, tolerance=0.1)


def test_cli_exit_codes_and_outputs(tmp_path, capsys):
    _capture(tmp_path / "BENCH_r01.json", 1,
             [_row("m_per_sec", 1e6, cal=20.0)])
    _capture(tmp_path / "BENCH_r02.json", 2,
             [_row("m_per_sec", 1e5, cal=20.0)])
    out_json = tmp_path / "trend.json"
    rc = main(["--root", str(tmp_path), "--json", str(out_json)])
    assert rc == 1  # 10x normalized drop beyond default tolerance
    payload = json.loads(out_json.read_text())
    assert payload["regressions"][0]["metric"] == "m_per_sec"
    assert [r["round"] for r in payload["rounds"]] == [1, 2]
    # gate-metrics filter quiets an unlisted metric
    assert main(["--root", str(tmp_path),
                 "--gate-metrics", "other_metric",
                 "--json", str(out_json)]) == 0
    # no captures -> usage error, not a crash
    assert main(["--root", str(tmp_path / "empty")]) == 2

