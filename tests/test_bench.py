"""bench.py section harness: schema, isolation, selection, the
TPU-only backend probe — plus the znicz-bench-diff regression gate over
round files.

Tier-1 (no TPU): the bench driver parses ONE JSON object per line, so
the section runner must emit exactly that — a ``{"metric": ...}``
record per succeeding section and an ``{"error": ..., "section": ...}``
record for a failing one, with every OTHER section's records intact.
Sections here are
monkeypatched fast fakes; the real measurement bodies never run.

``znicz-bench-diff`` (the bench trajectory's machine-readable gate)
is smoke-tested here in the same tier so a schema drift in either the
round files or the tool fails CI, not the next release round.
"""

import json

import pytest

import bench
from znicz_tpu.utils import bench_diff


def _collect(sections, only=None, budget_s=0):
    # budget_s=0 disables the per-section wall budget by default so the
    # schema tests stay timing-free; the timeout tests pass their own
    lines = []
    failed = bench.run_sections(
        sections=sections,
        only=only,
        emit_record=lambda rec: lines.append(json.dumps(rec)),
        budget_s=budget_s,
    )
    return lines, failed


def _ok_section(name, value):
    def fn(ctx):
        ctx[name] = value
        return [{"metric": name, "value": value, "unit": "u"}]

    return (name, fn)


def _boom_section(name, exc=RuntimeError):
    def fn(ctx):
        raise exc(f"{name} exploded")

    return (name, fn)


class TestSectionIsolation:
    def test_every_line_is_one_parseable_json_record(self):
        lines, failed = _collect(
            [_ok_section("a_rate", 1.5), _ok_section("b_rate", 2.5)]
        )
        assert failed == []
        assert len(lines) == 2
        for line in lines:
            rec = json.loads(line)  # one object per line, parseable
            assert "\n" not in line
            assert "metric" in rec and "value" in rec

    def test_one_failing_section_cannot_zero_the_run(self):
        # the BENCH_r05 regression shape: a mid-run failure must emit
        # its own error record and leave neighbors' records intact
        lines, failed = _collect(
            [
                _ok_section("before_rate", 1.0),
                _boom_section("flaky", RuntimeError),
                _ok_section("after_rate", 2.0),
            ]
        )
        assert failed == ["flaky"]
        recs = [json.loads(x) for x in lines]
        assert [r.get("metric") for r in recs] == [
            "before_rate", None, "after_rate",
        ]
        err = recs[1]
        assert err["error"] == "RuntimeError"
        assert err["section"] == "flaky"
        assert "exploded" in err["detail"]

    def test_only_prefix_selects_sections(self):
        sections = [
            _ok_section("lm_serve_rate", 1.0),
            _ok_section("lm_serve_paged_rate", 2.0),
            _ok_section("alexnet_rate", 3.0),
        ]
        lines, failed = _collect(sections, only="lm_serve")
        assert failed == []
        got = {json.loads(x)["metric"] for x in lines}
        assert got == {"lm_serve_rate", "lm_serve_paged_rate"}

    def test_registered_sections_cover_the_headline_metrics(self):
        names = [name for name, _ in bench._SECTIONS]
        assert names == sorted(set(names), key=names.index)  # unique
        for expected in (
            "alexnet_step", "lm_train", "lm_serve_paged",
            "lm_serve_prefix", "lm_serve_frontdoor",
        ):
            assert expected in names


class TestSectionBudget:
    def test_hung_section_times_out_and_round_continues(self):
        # the PR 5 leftover named in ROADMAP: a section that never
        # returns must emit its own timeout record and yield to the
        # next section instead of stalling the round forever
        import threading

        def hung(ctx):
            threading.Event().wait(timeout=30)  # "forever" at test scale
            return [{"metric": "never", "value": 0, "unit": "u"}]

        lines, failed = _collect(
            [
                _ok_section("before_rate", 1.0),
                ("stuck", hung),
                _ok_section("after_rate", 2.0),
            ],
            budget_s=0.3,
        )
        assert failed == ["stuck"]
        recs = [json.loads(x) for x in lines]
        assert [r.get("metric") for r in recs] == [
            "before_rate", None, "after_rate",
        ]
        assert recs[1] == {
            "error": "timeout", "section": "stuck", "budget_s": 0.3,
        }

    def test_fast_sections_are_untouched_by_the_budget(self):
        lines, failed = _collect(
            [_ok_section("quick_rate", 1.0)], budget_s=30.0
        )
        assert failed == []
        assert json.loads(lines[0])["metric"] == "quick_rate"


def _round_file(tmp_path, name, metrics, driver=True):
    """One bench round on disk, in either accepted shape."""
    path = tmp_path / name
    if driver:
        path.write_text(json.dumps({"rc": 0, "parsed": metrics}))
    else:
        lines = [
            json.dumps({"metric": k, "value": v, "unit": "u"})
            for k, v in metrics.items()
        ]
        path.write_text("\n".join(lines) + "\n")
    return str(path)


class TestBenchDiff:
    def test_clean_diff_exits_zero(self, tmp_path, capsys):
        old = _round_file(
            tmp_path, "old.json", {"lm_serve_tokens_per_sec": 100.0}
        )
        new = _round_file(
            tmp_path, "new.json", {"lm_serve_tokens_per_sec": 99.0}
        )
        assert bench_diff.main([old, new]) == 0
        out = capsys.readouterr().out
        assert "0 regression(s)" in out

    def test_throughput_drop_is_a_regression(self, tmp_path):
        old = _round_file(
            tmp_path, "old.json", {"lm_serve_tokens_per_sec": 100.0}
        )
        new = _round_file(
            tmp_path, "new.json", {"lm_serve_tokens_per_sec": 80.0}
        )
        assert bench_diff.main([old, new, "--threshold", "0.1"]) == 1
        # a looser threshold tolerates the same move
        assert bench_diff.main([old, new, "--threshold", "0.25"]) == 0

    def test_latency_shaped_metrics_regress_upward(self, tmp_path):
        old = _round_file(
            tmp_path, "old.json",
            {"lm_serve_frontdoor_ttft_p99_ms": 10.0, "step_ms": 5.0},
        )
        new = _round_file(
            tmp_path, "new.json",
            {"lm_serve_frontdoor_ttft_p99_ms": 15.0, "step_ms": 5.1},
        )
        # ttft +50% regresses; step_ms +2% is inside the threshold
        assert bench_diff.main([old, new]) == 1
        assert bench_diff.main(
            [old, new, "--only", "step_ms"]
        ) == 0

    def test_lower_better_from_zero_regresses(self, tmp_path):
        old = _round_file(
            tmp_path, "old.json", {"lm_serve_paged_compiles": 0.0}
        )
        new = _round_file(
            tmp_path, "new.json", {"lm_serve_paged_compiles": 2.0}
        )
        assert bench_diff.main([old, new]) == 1

    def test_ndjson_rounds_and_missing_metrics_tolerated(
        self, tmp_path, capsys
    ):
        old = _round_file(
            tmp_path, "old.json",
            {"a_rate_per_sec": 1.0, "only_old_per_sec": 3.0},
            driver=False,
        )
        new = _round_file(
            tmp_path, "new.json",
            {"a_rate_per_sec": 1.05, "only_new_per_sec": 9.0},
            driver=False,
        )
        assert bench_diff.main([old, new]) == 0
        out = capsys.readouterr().out
        assert "present in only one round" in out

    def test_error_records_skipped_in_ndjson(self, tmp_path):
        path = tmp_path / "mixed.json"
        path.write_text(
            json.dumps({"metric": "x_per_sec", "value": 2.0}) + "\n"
            + json.dumps({"error": "RuntimeError", "section": "s"})
            + "\n"
        )
        assert bench_diff.load_metrics(str(path)) == {"x_per_sec": 2.0}

    def test_direction_overrides(self, tmp_path):
        old = _round_file(tmp_path, "old.json", {"oddly_named": 10.0})
        new = _round_file(tmp_path, "new.json", {"oddly_named": 20.0})
        # default: higher-better, a rise is fine
        assert bench_diff.main([old, new]) == 0
        assert bench_diff.main(
            [old, new, "--lower", "oddly_named"]
        ) == 1

    def test_spec_metrics_are_higher_better(self, tmp_path):
        # ISSUE 12 satellite: the new speculative-serving metrics are
        # throughput-shaped — a DROP in acceptance rate or the
        # vs-baseline ratio is the regression, a rise never is
        for name in (
            "lm_serve_spec_acceptance_rate",
            "lm_serve_spec_vs_baseline",
        ):
            assert bench_diff.metric_direction(name, set(), set()) == (
                "higher"
            )
            old = _round_file(tmp_path, "old.json", {name: 1.0})
            new = _round_file(tmp_path, "new.json", {name: 0.5})
            assert bench_diff.main([old, new]) == 1  # drop regresses
            assert bench_diff.main([new, old]) == 0  # rise is fine
        # the marker beats embedded lower-better substrings ("_ms"
        # etc. never hijack an acceptance-rate family name)
        assert bench_diff.metric_direction(
            "spec_ttft_acceptance_rate", set(), set()
        ) == "higher"
        # while the spec COMPILE count stays lower-better
        assert bench_diff.metric_direction(
            "lm_serve_spec_compiles", set(), set()
        ) == "lower"

    def test_json_output_shape(self, tmp_path, capsys):
        old = _round_file(tmp_path, "old.json", {"r_per_sec": 1.0})
        new = _round_file(tmp_path, "new.json", {"r_per_sec": 0.5})
        assert bench_diff.main([old, new, "--json"]) == 1
        body = json.loads(capsys.readouterr().out)
        assert body["regressions"] == 1
        assert body["rows"][0]["metric"] == "r_per_sec"
        assert body["rows"][0]["regressed"] is True

    def test_usage_and_parse_errors_exit_two(self, tmp_path, capsys):
        assert bench_diff.main([]) == 2
        assert bench_diff.main(["one.json"]) == 2
        assert bench_diff.main(["a", "b", "--threshold"]) == 2
        bad = tmp_path / "bad.json"
        bad.write_text("not json at all")
        ok = _round_file(tmp_path, "ok.json", {"m_per_sec": 1.0})
        assert bench_diff.main([str(bad), ok]) == 2
        capsys.readouterr()  # drain stderr/stdout

    def test_fully_failed_round_fails_the_gate(self, tmp_path, capsys):
        """A round that crashed entirely (driver rc!=0, no parsed
        metrics — the committed BENCH_r05 shape) must NOT pass as
        '0 compared, 0 regressions': the gate exits 2."""
        failed = tmp_path / "failed.json"
        failed.write_text(
            json.dumps({"rc": 1, "cmd": "python bench.py",
                        "tail": "Traceback ...", "parsed": None})
        )
        ok = _round_file(tmp_path, "ok.json", {"m_per_sec": 1.0})
        assert bench_diff.main([ok, str(failed)]) == 2
        assert "no numeric metrics" in capsys.readouterr().err
        # all-error NDJSON is the same story
        errs = tmp_path / "errs.json"
        errs.write_text(
            json.dumps({"error": "RuntimeError", "section": "s"}) + "\n"
        )
        assert bench_diff.main([ok, str(errs)]) == 2
        capsys.readouterr()

    def test_program_headline_is_top_level_and_diffable(self, tmp_path):
        """The compile-ledger headline must ride as TOP-LEVEL numeric
        fields of the summary record (nested under metrics_snapshot it
        would be invisible to the flatten), and a compile-count rise
        must regress under the name heuristic."""
        headline = bench._program_headline()
        assert set(headline) >= {
            "programs_compiled", "programs_compile_seconds"
        }
        old = _round_file(
            tmp_path, "old.json",
            {"bench_sections_failed": 0, "programs_compiled": 3.0},
        )
        new = _round_file(
            tmp_path, "new.json",
            {"bench_sections_failed": 0, "programs_compiled": 5.0},
        )
        assert bench_diff.main([old, new]) == 1  # compiles grew: gate

    def test_driver_round_files_still_load(self, tmp_path):
        """Round files in the shape the driver writes must stay
        parseable — the tool is only a gate if it can read the
        artifacts the driver actually produces: numbers beside strings
        and nested breakdowns under ``parsed``, and ``parsed: null``
        for a round that failed."""

        def driver_round(n, rc, parsed):
            path = tmp_path / f"BENCH_r{n:02d}.json"
            path.write_text(
                json.dumps(
                    {
                        "n": n,
                        "cmd": "python bench.py",
                        "rc": rc,
                        "tail": "setup+compile 98.0s\n",
                        "parsed": parsed,
                    }
                )
            )
            return str(path)

        ok = {
            "metric": "alexnet_images_per_sec",
            "value": 100.0,
            "unit": "images/sec",
            "mfu": 0.4,
            "epoch_breakdown_s": {"metrics_sync": 0.5, "wall": 0.6},
            "lm_config": "512d x 12L",
            "device": "TPU v5 lite",
        }
        rounds = [
            driver_round(1, 0, ok),
            driver_round(2, 0, {**ok, "value": 110.0}),
            driver_round(3, 1, None),
        ]
        loaded = 0
        for path in rounds:
            try:
                metrics = bench_diff.load_metrics(path)
            except ValueError:
                continue  # an all-error round carries no metrics
            loaded += 1
            assert all(
                isinstance(v, float) for v in metrics.values()
            )
        assert loaded == 2  # enough history for a real diff
        assert bench_diff.main(rounds[:2]) == 0


class TestBackendProbe:
    class _Dev:
        def __init__(self, platform, kind):
            self.platform, self.device_kind = platform, kind

    def test_init_backend_refuses_a_cpu_device(self):
        with pytest.raises(RuntimeError, match="measures the TPU"):
            bench._init_backend(probe=lambda: [self._Dev("cpu", "cpu")])

    def test_init_backend_accepts_an_injected_tpu_device(self, capsys):
        devs = [self._Dev("tpu", "TPU v5 lite")]
        assert bench._init_backend(probe=lambda: devs) is devs
        assert "tpu TPU v5 lite x1" in capsys.readouterr().err
