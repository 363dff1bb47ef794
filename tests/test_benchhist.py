"""Schema, trajectory-store, and regression-detector tests for
``repro.tools.benchhist`` — the hardened harness around every speed claim.

Three layers, mirroring the module:

1. **Schema**: construction/parsing is strict (malformed and
   missing-field records raise :class:`BenchHistError` with actionable
   messages), serialization is byte-stable (serialize → parse →
   serialize is byte-identical), and a run's free-form ``context`` block
   is scrubbed with the same volatile-key filter as the stable artifacts.
2. **Trajectory store**: append/load round-trips, benchmark-name
   mismatches and invalid JSON are rejected naming the file and record.
3. **Detector**: unit tests for window/mode semantics plus property
   tests (via ``tests.proptest`` — hypothesis when installed, seeded
   sampling otherwise) for the statistical guarantees: bounded noise on
   a flat trajectory never fires, step regressions beyond tolerance
   always fire, direction awareness for ``higher_is_better=False``, and
   invariance to permutations of history outside the window.
"""

import json

import pytest

from repro.tools.benchhist import (
    DEFAULT_TOLERANCE,
    BenchHistError,
    BenchmarkSpec,
    BenchRun,
    Measurement,
    MeasurementSpec,
    append_run,
    detect_regressions,
    dumps_run,
    dumps_trajectory,
    gate_all,
    load_trajectory,
    loads_run,
    render_trends,
    resolve_path,
    scrub_volatile,
    trajectory_path,
)

from proptest import given, settings, st

ENV = {
    "git_sha": "deadbeef" * 5,
    "timestamp_utc": "2026-08-07T12:00:00+00:00",
    "platform": "Linux-test",
    "python": "3.11.0",
    "numpy": "1.26.0",
    "jax": None,
    "backend": "numpy",
}


def make_run(values, *, mode="smoke", higher_is_better=True, tolerance=None,
             benchmark="demo", timestamp="2026-08-07T12:00:00+00:00",
             context=None):
    """A BenchRun with one measurement per (name, value) pair."""
    if isinstance(values, (int, float)):
        values = {"metric": values}
    ms = tuple(
        Measurement(name, v, "rps", higher_is_better, tolerance=tolerance)
        for name, v in values.items())
    return BenchRun(benchmark=benchmark, mode=mode, git_sha=ENV["git_sha"],
                    timestamp_utc=timestamp, platform=ENV["platform"],
                    python=ENV["python"], numpy=ENV["numpy"],
                    jax=ENV["jax"], backend=ENV["backend"],
                    measurements=ms, context=context)


# ---------------------------------------------------------------------------
# schema: strict validation


@pytest.mark.parametrize("kwargs, fragment", [
    (dict(name="Bad-Name", value=1.0, unit="rps", higher_is_better=True),
     "name must match"),
    (dict(name="m", value=float("nan"), unit="rps", higher_is_better=True),
     "finite"),
    (dict(name="m", value="fast", unit="rps", higher_is_better=True),
     "expected a number"),
    (dict(name="m", value=1.0, unit="", higher_is_better=True),
     "non-empty"),
    (dict(name="m", value=1.0, unit="rps", higher_is_better=1),
     "must be a bool"),
    (dict(name="m", value=1.0, unit="rps", higher_is_better=True,
          tolerance=0.0), "tolerance must be in"),
    (dict(name="m", value=1.0, unit="rps", higher_is_better=True,
          tolerance=1.5), "tolerance must be in"),
])
def test_measurement_validation_rejects(kwargs, fragment):
    with pytest.raises(BenchHistError, match=fragment):
        Measurement(**kwargs)


def test_measurement_coerces_bool_value_to_float():
    m = Measurement("passed", True, "bool", True)
    assert m.value == 1.0 and isinstance(m.value, float)


def test_measurement_from_dict_rejects_missing_and_unknown_fields():
    with pytest.raises(BenchHistError, match=r"missing required field"):
        Measurement.from_dict({"name": "m", "value": 1.0})
    with pytest.raises(BenchHistError, match=r"unknown field.*wall_s"):
        Measurement.from_dict({"name": "m", "value": 1.0, "unit": "rps",
                               "higher_is_better": True, "wall_s": 0.5})
    with pytest.raises(BenchHistError, match="expected an object"):
        Measurement.from_dict([1, 2, 3])


def test_benchrun_validation_rejects():
    with pytest.raises(BenchHistError, match="mode must be one of"):
        make_run(1.0, mode="dev")
    with pytest.raises(BenchHistError, match="git_sha must be a non-empty"):
        BenchRun(
            benchmark="demo", mode="smoke", git_sha="",
            timestamp_utc=ENV["timestamp_utc"], platform="p", python="3",
            numpy="1", backend="numpy",
            measurements=(Measurement("m", 1.0, "rps", True),))
    with pytest.raises(BenchHistError, match="ISO-8601"):
        make_run(1.0, timestamp="yesterday")
    with pytest.raises(BenchHistError, match="must be non-empty"):
        BenchRun(benchmark="demo", mode="smoke", git_sha=ENV["git_sha"],
                 timestamp_utc=ENV["timestamp_utc"], platform="p",
                 python="3", numpy="1", backend="numpy", measurements=())
    with pytest.raises(BenchHistError, match=r"duplicate measurement"):
        BenchRun(benchmark="demo", mode="smoke", git_sha=ENV["git_sha"],
                 timestamp_utc=ENV["timestamp_utc"], platform="p",
                 python="3", numpy="1", backend="numpy",
                 measurements=(Measurement("m", 1.0, "rps", True),
                               Measurement("m", 2.0, "rps", True)))


def test_benchrun_from_dict_errors_are_actionable():
    good = make_run(1.0).to_dict()
    bad = dict(good)
    del bad["git_sha"]
    with pytest.raises(BenchHistError, match=r"missing required field.*git_sha"):
        BenchRun.from_dict(bad)
    bad = dict(good, extra_field=1)
    with pytest.raises(BenchHistError, match=r"unknown field.*extra_field"):
        BenchRun.from_dict(bad)
    bad = dict(good, measurements={"m": 1})
    with pytest.raises(BenchHistError, match="must be a list"):
        BenchRun.from_dict(bad)
    # the nested measurement error names its index
    bad = dict(good, measurements=[{"name": "m"}])
    with pytest.raises(BenchHistError, match=r"measurements\[0\]"):
        BenchRun.from_dict(bad)


def test_benchrun_context_is_scrubbed_of_volatile_keys():
    run = make_run(1.0, context={"artifact": "demo.json", "wall_s": 1.2,
                                 "nested": {"rps": 3.0, "kept": 7}})
    assert run.context == {"artifact": "demo.json", "nested": {"kept": 7}}
    # and the scrub is the same function the stable artifacts use
    assert scrub_volatile({"wall_s": 1, "kept": 2}) == {"kept": 2}


# ---------------------------------------------------------------------------
# schema: byte-stable serialization


def test_run_roundtrip_is_byte_identical():
    run = make_run({"a_rps": 123.456, "b_err": 0.001},
                   context={"artifact": "demo.json"})
    text = dumps_run(run)
    again = loads_run(text)
    assert again == run
    assert dumps_run(again) == text


def test_loads_run_rejects_invalid_json():
    with pytest.raises(BenchHistError, match="not valid JSON"):
        loads_run("{nope")


def test_golden_serialization():
    """The on-disk schema is an interface: fixed key order, fixed indent.
    If this golden changes, schema_version must be bumped."""
    run = make_run({"metric": 2.0})
    golden = json.dumps({
        "backend": "numpy",
        "benchmark": "demo",
        "git_sha": ENV["git_sha"],
        "jax": None,
        "measurements": [{
            "higher_is_better": True,
            "name": "metric",
            "unit": "rps",
            "value": 2.0,
        }],
        "mode": "smoke",
        "numpy": "1.26.0",
        "platform": "Linux-test",
        "python": "3.11.0",
        "timestamp_utc": "2026-08-07T12:00:00+00:00",
    }, sort_keys=True, indent=1)
    assert dumps_run(run) == golden


# ---------------------------------------------------------------------------
# trajectory store


def test_append_and_load_trajectory(tmp_path):
    r1 = make_run(10.0)
    r2 = make_run(11.0, timestamp="2026-08-07T13:00:00+00:00")
    path = append_run(tmp_path, r1)
    assert path == trajectory_path(tmp_path, "demo")
    append_run(tmp_path, r2)
    runs = load_trajectory(path)
    assert runs == [r1, r2]
    # the file itself is byte-stable: load → dump reproduces it
    assert dumps_trajectory("demo", runs) == path.read_text()


def test_load_trajectory_missing_file_names_the_remedy(tmp_path):
    with pytest.raises(BenchHistError, match="--record"):
        load_trajectory(tmp_path / "BENCH_demo.json")


def test_load_trajectory_rejects_malformed(tmp_path):
    p = tmp_path / "BENCH_demo.json"
    p.write_text("{invalid")
    with pytest.raises(BenchHistError, match="not valid JSON"):
        load_trajectory(p)
    p.write_text(json.dumps({"benchmark": "demo", "runs": []}))
    with pytest.raises(BenchHistError, match="schema_version"):
        load_trajectory(p)
    p.write_text(json.dumps({"schema_version": 99, "benchmark": "demo",
                             "runs": []}))
    with pytest.raises(BenchHistError, match="schema_version 99"):
        load_trajectory(p)
    # a record for the wrong benchmark names the index
    p.write_text(dumps_trajectory("demo", [make_run(1.0, benchmark="other")]))
    with pytest.raises(BenchHistError, match=r"runs\[0\].*'other'"):
        load_trajectory(p)


def test_load_trajectory_names_file_and_record_index(tmp_path):
    p = tmp_path / "BENCH_demo.json"
    good = make_run(1.0).to_dict()
    bad = dict(good)
    del bad["platform"]
    p.write_text(json.dumps({"schema_version": 1, "benchmark": "demo",
                             "runs": [good, bad]}))
    with pytest.raises(BenchHistError, match=r"runs\[1\].*platform"):
        load_trajectory(p)


# ---------------------------------------------------------------------------
# declaration layer


def test_resolve_path_and_errors():
    payload = {"a": {"b": [10, {"c": 42}]}}
    assert resolve_path(payload, "a.b.1.c") == 42
    assert resolve_path(payload, "a.b.0") == 10
    with pytest.raises(BenchHistError, match="not in"):
        resolve_path(payload, "a.missing")
    with pytest.raises(BenchHistError, match="does not index"):
        resolve_path(payload, "a.b.9")
    with pytest.raises(BenchHistError, match="reached a leaf"):
        resolve_path(payload, "a.b.0.c")


def test_measurement_spec_requires_exactly_one_source():
    with pytest.raises(BenchHistError, match="exactly one"):
        MeasurementSpec("m", "rps", True)
    with pytest.raises(BenchHistError, match="exactly one"):
        MeasurementSpec("m", "rps", True, path="a", extract=lambda p: 1.0)


def test_measurement_spec_missing_source_is_actionable():
    spec = MeasurementSpec("m", "rps", True, path="gone")
    with pytest.raises(BenchHistError, match="BENCH_SPEC"):
        spec.measure({"present": 1})
    assert MeasurementSpec("m", "rps", True, path="gone",
                           optional=True).measure({}) is None
    # extract callables that poke a vanished row are wrapped the same way
    bad = MeasurementSpec("m", "rps", True,
                          extract=lambda rows: next(
                              r for r in rows if r["variant"] == "gone"))
    with pytest.raises(BenchHistError, match="BENCH_SPEC"):
        bad.measure([{"variant": "here"}])


def test_benchmark_spec_mode_filtering():
    spec = BenchmarkSpec(
        artifact="full.json", smoke_artifact="smoke.json",
        measurements=(
            MeasurementSpec("always", "rps", True, path="a"),
            MeasurementSpec("full_only", "rps", True, path="b", smoke=False),
            MeasurementSpec("wallclock", "rps", True, path="a",
                            volatile=True),
        ))
    assert spec.artifact_for("full") == "full.json"
    assert spec.artifact_for("smoke") == "smoke.json"
    names = lambda mode, iv: [s.name for s in
                              spec.specs_for(mode, include_volatile=iv)]
    assert names("full", True) == ["always", "full_only", "wallclock"]
    assert names("smoke", True) == ["always", "wallclock"]
    assert names("smoke", False) == ["always"]
    got = spec.collect({"a": 1.0, "b": 2.0}, "smoke")
    assert [m.name for m in got] == ["always", "wallclock"]


# ---------------------------------------------------------------------------
# detector: unit tests


def ts(i):
    return f"2026-08-07T{i:02d}:00:00+00:00"


def flat_then(values, last, **kw):
    """A trajectory of constant `values` with `last` appended."""
    runs = [make_run(v, timestamp=ts(i)) for i, v in enumerate(values)]
    runs.append(make_run(last, timestamp=ts(len(values)), **kw))
    return runs


def test_detector_passes_with_no_history():
    assert detect_regressions([make_run(1.0)]) == []
    assert detect_regressions([]) == []


def test_detector_fires_on_step_regression_and_names_it():
    runs = flat_then([100.0] * 4, 50.0)
    v = detect_regressions(runs)
    assert len(v) == 1
    assert v[0].measurement == "metric"
    assert "fell below" in v[0].describe()
    assert "metric" in v[0].describe()


def test_detector_tolerates_within_tolerance_dip():
    assert detect_regressions(flat_then([100.0] * 4, 71.0)) == []
    assert detect_regressions(flat_then([100.0] * 4, 69.0))


def test_detector_direction_aware_for_lower_is_better():
    runs = [make_run(100.0, higher_is_better=False, timestamp=ts(i))
            for i in range(4)]
    runs.append(make_run(150.0, higher_is_better=False, timestamp=ts(4)))
    v = detect_regressions(runs)
    assert len(v) == 1 and "rose above" in v[0].describe()
    # a *drop* in a lower-is-better metric is an improvement, not a violation
    runs[-1] = make_run(10.0, higher_is_better=False, timestamp=ts(4))
    assert detect_regressions(runs) == []


def test_detector_per_measurement_tolerance_overrides_default():
    # 10% dip: default 30% tolerance passes, 5% per-measurement fires
    assert detect_regressions(flat_then([100.0] * 4, 90.0)) == []
    assert detect_regressions(flat_then([100.0] * 4, 90.0, tolerance=0.05))


def test_detector_only_gates_same_mode_history():
    runs = [make_run(1000.0, mode="full", timestamp=ts(i)) for i in range(4)]
    runs.append(make_run(100.0, mode="smoke", timestamp=ts(4)))
    # smoke current, full-only history: nothing to compare against
    assert detect_regressions(runs) == []


def test_detector_new_measurement_passes():
    runs = [make_run({"old": 100.0}, timestamp=ts(0)),
            make_run({"old": 100.0, "new": 5.0}, timestamp=ts(1))]
    assert detect_regressions(runs) == []


def test_detector_window_excludes_ancient_history():
    # 5 recent good runs push the ancient 1000.0 out of the window
    runs = flat_then([1000.0] + [100.0] * 5, 95.0)
    assert detect_regressions(runs, window=5) == []
    # with a window wide enough to see 1000.0 the median is still 100.0
    # (median is robust to the single outlier) — widen the regression
    runs = flat_then([1000.0] * 3 + [100.0] * 3, 95.0)
    assert detect_regressions(runs, window=6)


def test_detector_validates_its_knobs():
    with pytest.raises(BenchHistError, match="window"):
        detect_regressions([], window=0)
    with pytest.raises(BenchHistError, match="default_tolerance"):
        detect_regressions([], default_tolerance=0.0)


# ---------------------------------------------------------------------------
# detector: property tests (hypothesis when available, seeded otherwise)


@settings(max_examples=40)
@given(st.lists(st.floats(min_value=-0.15, max_value=0.15), min_size=2,
                max_size=12),
       st.floats(min_value=10.0, max_value=1e6))
def test_prop_bounded_noise_on_flat_trajectory_never_fires(noise, base):
    """Relative noise within ±15% of a flat baseline stays inside the 30%
    default tolerance of the window median, whatever the window contents
    (at ±20% it need not: 0.8b against a median of 1.2b is 0.67x)."""
    runs = [make_run(base * (1.0 + n), timestamp=ts(i % 24))
            for i, n in enumerate(noise)]
    # median of history in [0.85b, 1.15b]; current >= 0.85b > 0.7 * 1.15b
    assert detect_regressions(runs) == []


@settings(max_examples=40)
@given(st.lists(st.floats(min_value=-0.05, max_value=0.05), min_size=1,
                max_size=8),
       st.floats(min_value=10.0, max_value=1e6),
       st.floats(min_value=0.35, max_value=0.95))
def test_prop_step_regression_beyond_tolerance_always_fires(noise, base, drop):
    """A drop strictly beyond tolerance + noise band must always fire."""
    runs = [make_run(base * (1.0 + n), timestamp=ts(i % 24))
            for i, n in enumerate(noise)]
    runs.append(make_run(base * (1.0 - drop), timestamp=ts(23)))
    # median >= 0.95*base; current <= 0.65*base < 0.7 * median
    assert detect_regressions(runs), (noise, base, drop)


@settings(max_examples=40)
@given(st.lists(st.floats(min_value=-0.05, max_value=0.05), min_size=1,
                max_size=8),
       st.floats(min_value=10.0, max_value=1e6),
       st.floats(min_value=0.37, max_value=0.95))
def test_prop_direction_aware_lower_is_better(noise, base, rise):
    """For higher_is_better=False the SAME relative move flips verdicts:
    a rise beyond tolerance fires, the mirrored drop never does.  The rise
    starts at 0.37 so that it clears the 30% tolerance over a history
    median up to 5% high (1.05 * 1.3 = 1.365)."""
    hist = [make_run(base * (1.0 + n), higher_is_better=False,
                     timestamp=ts(i % 24)) for i, n in enumerate(noise)]
    worse = hist + [make_run(base * (1.0 + rise), higher_is_better=False,
                             timestamp=ts(23))]
    better = hist + [make_run(base * (1.0 - rise) if rise < 1 else 0.0,
                              higher_is_better=False, timestamp=ts(23))]
    assert detect_regressions(worse)
    assert detect_regressions(better) == []


@settings(max_examples=25)
@given(st.lists(st.floats(min_value=10.0, max_value=1e6), min_size=8,
                max_size=14),
       st.integers(min_value=0, max_value=10**6))
def test_prop_history_outside_window_is_irrelevant(values, seed):
    """Permuting (or rewriting) entries older than the window cannot change
    the verdict — the gate sees only the last `window` same-mode runs."""
    import random

    window = 5
    runs = [make_run(v, timestamp=ts(i % 24)) for i, v in enumerate(values)]
    before = bool(detect_regressions(runs, window=window))
    head = values[:-(window + 1)]
    tail = values[-(window + 1):]
    rng = random.Random(seed)
    shuffled = head[:]
    rng.shuffle(shuffled)
    # also rewrite the pre-window values entirely: replace with constants
    for head2 in (shuffled, [1.0] * len(head)):
        runs2 = [make_run(v, timestamp=ts(i % 24))
                 for i, v in enumerate(head2 + tail)]
        assert bool(detect_regressions(runs2, window=window)) == before


@settings(max_examples=25)
@given(st.lists(st.floats(min_value=10.0, max_value=1e6), min_size=2,
                max_size=10),
       st.floats(min_value=1.0, max_value=10.0))
def test_prop_improvements_never_fire(values, gain):
    """A current value at or above the history median can never violate a
    higher-is-better gate."""
    runs = [make_run(v, timestamp=ts(i % 24)) for i, v in enumerate(values)]
    import statistics

    med = statistics.median(v for v in values[:-1][-5:])
    runs[-1] = make_run(med * gain, timestamp=ts(23))
    assert detect_regressions(runs) == []


# ---------------------------------------------------------------------------
# gate_all + trend rendering


def test_gate_all_ok_and_regression(tmp_path, capsys):
    for i, v in enumerate([100.0, 101.0, 99.0]):
        append_run(tmp_path, make_run(v, timestamp=ts(i)))
    lines = []
    assert gate_all(tmp_path, log=lines.append) == 0
    assert any("demo: OK" in l for l in lines)
    assert any("gate-all: OK" in l for l in lines)

    append_run(tmp_path, make_run(10.0, timestamp=ts(5)))
    lines = []
    assert gate_all(tmp_path, log=lines.append) == 1
    joined = "\n".join(lines)
    assert "REGRESSION" in joined and "demo.metric" in joined
    assert "FAILED" in joined


def test_gate_all_empty_dir_fails(tmp_path):
    lines = []
    assert gate_all(tmp_path, log=lines.append) == 1
    assert "no BENCH_" in lines[0]


def test_gate_all_malformed_trajectory_fails(tmp_path):
    (tmp_path / "BENCH_demo.json").write_text("{broken")
    lines = []
    assert gate_all(tmp_path, log=lines.append) == 1
    assert any("MALFORMED" in l for l in lines)


def test_gate_all_lists_every_violation(tmp_path):
    for i in range(3):
        append_run(tmp_path, make_run({"a": 100.0, "b": 200.0},
                                      timestamp=ts(i)))
    append_run(tmp_path, make_run({"a": 1.0, "b": 2.0}, timestamp=ts(4)))
    lines = []
    assert gate_all(tmp_path, log=lines.append) == 1
    joined = "\n".join(lines)
    assert "demo.a" in joined and "demo.b" in joined


def test_render_trends(tmp_path):
    for i, v in enumerate([100.0, 110.0]):
        append_run(tmp_path, make_run(v, timestamp=ts(i)))
    lines = render_trends(tmp_path)
    joined = "\n".join(lines)
    assert "BENCH_demo.json" in joined
    assert "| metric |" in joined
    assert "100 → 110" in joined
    assert render_trends(tmp_path / "empty") == []
