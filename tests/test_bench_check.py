"""``bench.py --check`` (docs/lint.md "CI"): the regression gate against
the newest ``BENCH_r*.json`` capture.

The comparison core (``compare_rows``) and baseline recovery
(``load_baseline_summary``) are pure functions unit-tested here without
running a benchmark; the ``@slow`` test drives one real row end-to-end
through ``main(["--check", ...])`` against synthetic baselines.
"""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:  # bench.py is a repo-root module
    sys.path.insert(0, ROOT)

import bench  # noqa: E402


def _row(short="smallnet_b64", value=10.0, unit="ms/batch", mfu=0.05,
         lo=None, hi=None):
    d = {"short": short, "value": value, "unit": unit, "mfu": mfu}
    if lo is not None:
        d["ms_min"], d["ms_max"] = lo, hi
    return d


BASE = {"smallnet_b64": [10.0, 0.05, None]}


# ---------------------------------------------------------------------------
# compare_rows: direction, guard, MFU, error handling
# ---------------------------------------------------------------------------


def test_latency_regression_fails():
    failures, checked, skipped = bench.compare_rows([_row(value=12.0)],
                                                    BASE)
    assert checked == ["smallnet_b64"] and not skipped
    assert failures and "1.200x" in failures[0]


def test_within_guard_passes():
    failures, checked, _ = bench.compare_rows([_row(value=10.9)], BASE)
    assert not failures and checked == ["smallnet_b64"]


def test_latency_improvement_never_fails():
    failures, _, _ = bench.compare_rows([_row(value=5.0, mfu=0.10)], BASE)
    assert not failures


def test_throughput_direction_is_inverted():
    base = {"seq2seq": [1000.0, 0.1, None]}
    f, _, _ = bench.compare_rows(
        [_row("seq2seq", 800.0, "words/s", 0.1)], base)
    assert f  # a words/s DROP is a regression
    f, _, _ = bench.compare_rows(
        [_row("seq2seq", 2000.0, "words/s", 0.1)], base)
    assert not f  # a rise is not


def test_rep_spread_widens_the_guard():
    # a 25% delta cannot be condemned by a run whose own reps
    # disagree by 40%
    f, _, _ = bench.compare_rows(
        [_row(value=12.5, lo=10.0, hi=14.0)], BASE)
    assert not f


def test_mfu_regression_fails_independently_of_value():
    f, _, _ = bench.compare_rows([_row(value=10.0, mfu=0.01)], BASE)
    assert f and "MFU" in f[0]


def test_errored_fresh_row_is_a_failure_not_a_skip():
    f, checked, skipped = bench.compare_rows(
        [{"short": "smallnet_b64", "value": None, "unit": "ERROR",
          "error": "RuntimeError: boom"}], BASE)
    assert f and "errored" in f[0]
    assert not checked and not skipped


def test_row_missing_from_baseline_is_skipped():
    f, checked, skipped = bench.compare_rows([_row("brand_new_row")], BASE)
    assert not f and not checked and skipped == ["brand_new_row"]


def test_errored_baseline_entry_is_skipped():
    f, _, skipped = bench.compare_rows(
        [_row()], {"smallnet_b64": "ERROR"})
    assert not f and skipped == ["smallnet_b64"]


# ---------------------------------------------------------------------------
# baseline recovery: raw line, driver wrapper, truncated tail
# ---------------------------------------------------------------------------


def test_load_baseline_raw_and_wrapped(tmp_path):
    raw = tmp_path / "BENCH_raw.json"
    raw.write_text(json.dumps({"device": "cpu", "summary": BASE}))
    assert bench.load_baseline_summary(str(raw)) == BASE
    wrapped = tmp_path / "BENCH_wrapped.json"
    wrapped.write_text(json.dumps({"n": 1, "rc": 0,
                                   "parsed": {"summary": BASE}}))
    assert bench.load_baseline_summary(str(wrapped)) == BASE


def test_load_baseline_recovers_summary_from_truncated_tail(tmp_path):
    # summary is emitted LAST in bench.py's capture line precisely so
    # a ~2000-char tail truncation keeps it regex-recoverable
    line = json.dumps({"rows": ["x" * 3000],
                       "summary": {"seq2seq": [1.0, None, None]}})
    doc = {"n": 2, "cmd": "bench", "rc": 0, "tail": line[-2000:],
           "parsed": None}
    p = tmp_path / "BENCH_r99.json"
    p.write_text(json.dumps(doc))
    assert bench.load_baseline_summary(str(p)) == \
        {"seq2seq": [1.0, None, None]}


def test_load_baseline_without_summary_raises(tmp_path):
    p = tmp_path / "b.json"
    p.write_text(json.dumps({"tail": "garbage", "parsed": None}))
    with pytest.raises(ValueError):
        bench.load_baseline_summary(str(p))


def test_newest_baseline_picks_highest_round(tmp_path):
    for n in ("BENCH_r01.json", "BENCH_r03.json", "BENCH_r02.json"):
        (tmp_path / n).write_text("{}")
    assert bench.newest_baseline(str(tmp_path)).endswith("BENCH_r03.json")


def test_repo_newest_capture_is_recoverable():
    """The real newest BENCH_r*.json at the repo root must yield a
    non-empty summary — the gate has a baseline to stand on."""
    summ = bench.load_baseline_summary(bench.newest_baseline(ROOT))
    assert isinstance(summ, dict) and summ
    assert all(isinstance(k, str) for k in summ)


def test_check_unknown_row_is_usage_error(tmp_path, capsys):
    base = tmp_path / "BENCH_r01.json"
    base.write_text(json.dumps({"summary": BASE}))
    rc = bench.main(["--check", "--rows", "no_such_row",
                     "--baseline", str(base)])
    assert rc == 2
    assert "unknown rows" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# end-to-end: one real row through main(["--check", ...])
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("argv", [[], ["--check", "--rows", "smallnet_b64"]],
                         ids=["capture", "check"])
def test_no_tpu_is_a_failure_not_a_cpu_capture(argv, capsys):
    """A measurement path that finds no chip fails and names the device it
    did find — it never prints CPU timings under device metric names."""
    with pytest.raises(SystemExit) as e:
        bench.main(argv)
    assert "no TPU found" in str(e.value)
    err = capsys.readouterr().err
    assert '"platform": "cpu"' in err and '"count": 8' in err


@pytest.mark.slow
def test_check_end_to_end_smallnet(tmp_path, capsys, monkeypatch):
    """Measure smallnet_b64 against a generous baseline (rc 0), then
    against an unbeatable one (rc 1) — the full gate wiring (the TPU
    requirement stepped over: this drives the wiring, not a timing)."""
    monkeypatch.setattr(bench, "_require_tpu", lambda: {
        "platform": "cpu", "kind": "cpu", "count": 1})
    good = tmp_path / "BENCH_r01.json"
    good.write_text(json.dumps(
        {"summary": {"smallnet_b64": [1e9, 1e-9, None]}}))
    rc = bench.main(["--check", "--rows", "smallnet_b64",
                     "--baseline", str(good)])
    rep = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and rep["ok"]
    assert rep["checked"] == ["smallnet_b64"] and not rep["failures"]

    bad = tmp_path / "BENCH_r02.json"
    bad.write_text(json.dumps(
        {"summary": {"smallnet_b64": [1e-9, 1.0, None]}}))
    rc = bench.main(["--check", "--rows", "smallnet_b64",
                     "--baseline", str(bad)])
    rep = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 1 and rep["failures"]
