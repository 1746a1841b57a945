"""Timed autotuning + TuneDB: the race, the disk cache, and the perf gate.

The race itself is tested with *scripted* timers (`timer(fn, blocks)`
injection) so outcomes are deterministic — who wins is the script's
choice, not the wall clock's. What's under test is the selection logic:
the measured winner is kept, the default lane can win, a lane that throws
cannot, and the tuned <= default invariant holds by construction.

DB tests cover the persistence contract: round-trip, warm-start without
re-racing (the second-benchmark-run-is-race-free property), corrupt and
stale-schema files degrading to cold autotune, and frozen mode never
touching disk. Cluster tests pin the exact counter traffic.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

# benchmarks/ is a namespace package off the repo root
ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from repro.cluster import Cluster, KernelPolicy, use_policy  # noqa: E402
from repro.configs import registry  # noqa: E402
from repro.kernels import ops, pipeline as pp, tunedb  # noqa: E402

SHAPES = {"m": 512, "n": 512, "k": 512}
KEY = pp.shape_key(SHAPES, 4)
BACKEND = jax.default_backend()


@pytest.fixture(autouse=True)
def _clean_tunes():
    registry.KERNEL_TUNES.clear()
    tunedb.set_active_db(None)
    yield
    registry.KERNEL_TUNES.clear()
    tunedb.reset_active_db()


def scripted_timer(script: dict, default: float = 1.0):
    """timer(fn, blocks) that never runs fn — returns scripted seconds."""
    def timer(fn, blocks):
        return script.get(tuple(sorted(blocks.items())), default)
    return timer


def modeled_pick(kernel: str = "matmul", shapes: dict = SHAPES) -> dict:
    return dict(pp.autotune(kernel, shapes, mode="modeled",
                            register_record=False).blocks)


# ----------------------------------------------------------------------------
# the race
# ----------------------------------------------------------------------------

def test_race_picks_fastest_candidate():
    """The scripted-fastest lane (here: the modeled-best candidate) wins,
    and the record carries real measured_us/default_us from the race."""
    best = modeled_pick()
    default = pp.KERNELS["matmul"].default_blocks(SHAPES)
    assert best != default          # 512^3: model prefers bigger tiles
    script = {tuple(sorted(best.items())): 0.5,
              tuple(sorted(default.items())): 2.0}
    r = pp.autotune("matmul", SHAPES, mode="timed",
                    timer=scripted_timer(script))
    assert r.source == "timed" and r.raced >= 2
    assert r.blocks == best
    assert r.measured_us == pytest.approx(0.5e6)
    assert r.default_us == pytest.approx(2.0e6)
    assert r.measured_speedup == pytest.approx(4.0)
    rec = registry.get_kernel_tune("matmul", KEY)
    assert rec.timed and rec.source == "timed"
    assert rec.measured_speedup == pytest.approx(4.0)


def test_race_default_lane_can_win():
    """When the default times fastest, the tuner keeps it — tuned is never
    slower than default because default is itself a race lane."""
    default = pp.KERNELS["matmul"].default_blocks(SHAPES)
    script = {tuple(sorted(default.items())): 0.1}
    r = pp.autotune("matmul", SHAPES, mode="timed",
                    timer=scripted_timer(script, default=1.0))
    assert r.blocks == dict(default)
    assert r.measured_us == r.default_us == pytest.approx(0.1e6)
    assert r.measured_speedup == pytest.approx(1.0)
    assert r.measured_us <= r.default_us


def test_race_erroring_lane_cannot_win():
    """A lane whose timer throws is scored inf; the survivors race on. Each
    refused lane is counted and reported with its error's first line."""
    default = pp.KERNELS["matmul"].default_blocks(SHAPES)
    default_key = tuple(sorted(default.items()))

    def timer(fn, blocks):
        if tuple(sorted(blocks.items())) != default_key:
            raise RuntimeError("candidate refused to compile\nsecond line")
        return 0.3
    pol = KernelPolicy()
    with use_policy(pol):
        r = pp.autotune("matmul", SHAPES, mode="timed", timer=timer)
    assert r.source == "timed"
    assert r.blocks == dict(default)
    assert len(r.refused) == r.raced - 1 >= 1
    assert pol.stats["tune_lane_failures"] == len(r.refused)
    assert all(line.startswith("matmul {") and line.endswith(
        "RuntimeError: candidate refused to compile") for line in r.refused)


def test_race_all_lanes_failing_falls_back_to_modeled():
    """There is no fallback: when every lane fails, the race raises with
    each lane's error instead of keeping a pick nobody has seen run."""
    def timer(fn, blocks):
        raise RuntimeError("no lane runs")
    pol = KernelPolicy()
    with use_policy(pol), pytest.raises(pp.RaceFailed,
                                        match="no lane runs") as exc:
        pp.autotune("matmul", SHAPES, mode="timed", timer=timer)
    n = pol.stats["tune_lane_failures"]
    assert n >= 2 and str(exc.value).count("no lane runs") == n
    assert registry.get_kernel_tune("matmul", KEY) is None


def test_autotune_raises_when_budget_admits_nothing():
    with pytest.raises(ValueError, match="VMEM budget"):
        pp.autotune("matmul", SHAPES, mode="modeled", vmem_budget=1024,
                    register_record=False)


def test_modeled_mode_never_races():
    def timer(fn, blocks):              # must never be consulted
        raise AssertionError("modeled mode raced")
    r = pp.autotune("matmul", SHAPES, mode="modeled", timer=timer)
    assert r.source == "modeled" and r.raced == 0 and r.measured_us == 0.0


def test_timed_race_on_device_tuned_not_slower(monkeypatch):
    """One real (unscripted) race: the acceptance invariant, measured."""
    monkeypatch.setenv("REPRO_TUNE_REPS", "1")
    r = pp.autotune("matmul", {"m": 256, "n": 256, "k": 256}, mode="timed")
    assert r.source == "timed" and r.raced >= 1
    assert r.measured_us <= r.default_us * (1 + 1e-9)
    assert r.measured_speedup >= 1.0


# ----------------------------------------------------------------------------
# the composition lane: fused-vs-unfused routing
# ----------------------------------------------------------------------------

COMP_KEY = tuple(sorted(pp.COMPOSITION_LANE.items()))


def test_composition_lane_wins_routes_unfused():
    """When a fused kernel's unfused composition times fastest, the race
    demotes the fusion: route flips to "unfused", measured_us is the
    composition's time, and the best *kernel* blocking is still recorded."""
    best = modeled_pick("rmsnorm_matmul")
    script = {COMP_KEY: 0.1}
    r = pp.autotune("rmsnorm_matmul", SHAPES, mode="timed",
                    timer=scripted_timer(script, default=1.0))
    assert r.source == "timed" and r.route == "unfused"
    assert r.measured_us == pytest.approx(0.1e6)
    assert r.blocks == best
    rec = registry.get_kernel_tune("rmsnorm_matmul", pp.shape_key(SHAPES, 4))
    assert rec.route == "unfused" and rec.timed


def test_composition_lane_losing_keeps_fused_route():
    best = modeled_pick("rmsnorm_matmul")
    script = {COMP_KEY: 5.0, tuple(sorted(best.items())): 0.5}
    r = pp.autotune("rmsnorm_matmul", SHAPES, mode="timed",
                    timer=scripted_timer(script, default=1.0))
    assert r.route == "fused"
    assert r.blocks == best
    assert r.measured_us == pytest.approx(0.5e6)
    rec = registry.get_kernel_tune("rmsnorm_matmul", pp.shape_key(SHAPES, 4))
    assert rec.route == "fused"


def test_composition_lane_erroring_keeps_fused_route():
    """A composition that won't run can't win — scored inf like any lane."""
    def timer(fn, blocks):
        if tuple(sorted(blocks.items())) == COMP_KEY:
            raise RuntimeError("composition refused to compile")
        return 1.0
    r = pp.autotune("rmsnorm_matmul", SHAPES, mode="timed", timer=timer)
    assert r.source == "timed" and r.route == "fused"


def test_unfused_kernel_has_no_composition_lane():
    """matmul carries no composition; the sentinel never reaches the timer."""
    def timer(fn, blocks):
        assert "route" not in blocks
        return 1.0
    r = pp.autotune("matmul", SHAPES, mode="timed", timer=timer)
    assert r.source == "timed" and r.route == "fused"


def test_route_survives_db_round_trip(tmp_path):
    pp.autotune("rmsnorm_matmul", SHAPES, mode="timed",
                timer=scripted_timer({COMP_KEY: 0.1}, default=1.0))
    rec = registry.get_kernel_tune("rmsnorm_matmul", pp.shape_key(SHAPES, 4))
    assert rec.route == "unfused"
    path = tmp_path / "tunes.json"
    tunedb.TuneDB(path).record(rec, backend=BACKEND, mode="tuned")
    got = tunedb.TuneDB(path).get(BACKEND, "tuned", "rmsnorm_matmul",
                                  pp.shape_key(SHAPES, 4))
    assert got == rec and got.route == "unfused"


def test_policy_dispatches_composition_on_unfused_route():
    """tuned_call honors a demoted fusion: the unfused composition runs
    (unfused_routes counter) and the numerics still match the reference."""
    m = k = n = 256
    x = jax.random.normal(jax.random.PRNGKey(0), (m, k), jnp.float32)
    scale = jnp.ones((k,), jnp.float32) * 0.1
    w = jax.random.normal(jax.random.PRNGKey(1), (k, n), jnp.float32)
    shapes = ops.kernel_shapes("rmsnorm_matmul", x, scale, w)
    pp.autotune("rmsnorm_matmul", shapes, mode="timed",
                timer=scripted_timer({COMP_KEY: 0.1}, default=1.0))
    pol = KernelPolicy(mode="tuned")
    with use_policy(pol):
        out = ops.tuned_call("rmsnorm_matmul", x, scale, w)
    assert pol.stats.get("unfused_routes") == 1
    assert pol.stats.get("tune_hits") == 1
    ref = ops.OPS["rmsnorm_matmul"].reference(x, scale, w)
    assert jnp.allclose(out, ref, atol=2e-2, rtol=2e-2)


# ----------------------------------------------------------------------------
# TuneDB persistence
# ----------------------------------------------------------------------------

def _timed_record() -> registry.KernelTuneRecord:
    script = {tuple(sorted(modeled_pick().items())): 0.5}
    pp.autotune("matmul", SHAPES, mode="timed",
                timer=scripted_timer(script, default=2.0))
    return registry.get_kernel_tune("matmul", KEY)


def test_db_round_trip(tmp_path):
    rec = _timed_record()
    path = tmp_path / "tunes.json"
    db = tunedb.TuneDB(path)
    db.record(rec, backend=BACKEND, mode="tuned")
    assert path.exists() and db.stores == 1

    db2 = tunedb.TuneDB(path)
    assert len(db2) == 1 and db2.loads == 1 and db2.load_errors == 0
    got = db2.get(BACKEND, "tuned", "matmul", KEY)
    assert got == rec               # full field-for-field round trip
    assert got.measured_speedup == pytest.approx(rec.measured_speedup)
    # other (backend, mode) keys don't alias
    assert db2.get(BACKEND, "fused", "matmul", KEY) is None
    assert db2.get("tpu" if BACKEND != "tpu" else "cpu",
                   "tuned", "matmul", KEY) is None


def test_db_warm_start_no_rerace(tmp_path):
    rec = _timed_record()
    path = tmp_path / "tunes.json"
    tunedb.TuneDB(path).record(rec, backend=BACKEND, mode="tuned")

    # fresh process simulation: empty registry, warm DB
    registry.KERNEL_TUNES.clear()
    db = tunedb.TuneDB(path)
    assert db.warm_start(backend=BACKEND, mode="tuned") == 1
    warm = registry.get_kernel_tune("matmul", KEY)
    assert warm.source == "db" and warm.timed
    assert dict(warm.blocks) == dict(rec.blocks)

    def timer(fn, blocks):
        raise AssertionError("warm-started record re-raced")
    with tunedb.use_db(db):
        got = pp.tuned_record("matmul", SHAPES, timer=timer, mode="timed")
    assert got is warm              # registry hit, no autotune at all

    # in-memory records take precedence over a second warm-start
    assert db.warm_start(backend=BACKEND, mode="tuned") == 0


def test_corrupt_db_falls_back_cold(tmp_path):
    path = tmp_path / "tunes.json"
    path.write_text("{not json")
    db = tunedb.TuneDB(path)
    assert len(db) == 0 and db.load_errors == 1
    assert db.warm_start(backend=BACKEND, mode="tuned") == 0
    # cold autotune still works and can repair the file
    rec = _timed_record()
    db.record(rec, backend=BACKEND, mode="tuned")
    assert len(tunedb.TuneDB(path)) == 1


def test_stale_schema_db_ignored(tmp_path):
    path = tmp_path / "tunes.json"
    path.write_text(json.dumps({"version": 999, "records": [{"bogus": 1}]}))
    db = tunedb.TuneDB(path)
    assert len(db) == 0 and db.load_errors == 1
    # a save rewrites the current schema
    db.save()
    assert json.loads(path.read_text())["version"] == tunedb.SCHEMA_VERSION


def test_frozen_db_never_writes(tmp_path):
    rec = _timed_record()
    path = tmp_path / "tunes.json"
    db = tunedb.TuneDB(path, frozen=True)
    db.record(rec, backend=BACKEND, mode="tuned")
    db.save()
    assert not path.exists()
    assert db.stores == 0 and db.write_skips == 2


def test_frozen_mode_autotune_no_race_no_write(tmp_path):
    path = tmp_path / "tunes.json"
    db = tunedb.TuneDB(path)

    def timer(fn, blocks):
        raise AssertionError("frozen mode raced")
    with tunedb.use_db(db):
        r = pp.autotune("matmul", SHAPES, mode="frozen", timer=timer)
    assert r.source == "modeled" and r.raced == 0
    assert len(db) == 0 and not path.exists()


def test_autotune_writes_through_active_db(tmp_path):
    path = tmp_path / "tunes.json"
    db = tunedb.TuneDB(path)
    script = {tuple(sorted(modeled_pick().items())): 0.5}
    with tunedb.use_db(db):
        pp.autotune("matmul", SHAPES, mode="timed",
                    timer=scripted_timer(script, default=2.0))
    assert len(db) == 1 and path.exists()
    got = db.get(BACKEND, "tuned", "matmul", KEY)
    assert got is not None and got.source == "timed"


def test_modeled_pick_not_written_to_db(tmp_path):
    """Only timed picks persist — a modeled pick must not poison later
    warm-starts with an unmeasured blocking."""
    db = tunedb.TuneDB(tmp_path / "tunes.json")
    with tunedb.use_db(db):
        pp.autotune("matmul", SHAPES, mode="modeled")
    assert len(db) == 0


def test_tune_mode_resolution(monkeypatch):
    monkeypatch.delenv("REPRO_TUNE_MODE", raising=False)
    assert tunedb.tune_mode() == "timed"
    monkeypatch.setenv("REPRO_TUNE_MODE", "frozen")
    assert tunedb.tune_mode() == "frozen"
    assert tunedb.tune_mode("modeled") == "modeled"   # explicit arg wins
    # policy.tuning outranks the env
    with use_policy(KernelPolicy(mode="tuned", tuning="timed")):
        assert tunedb.tune_mode() == "timed"
    with pytest.raises(ValueError):
        tunedb.tune_mode("warp")


# ----------------------------------------------------------------------------
# Cluster integration: counters + warm-start
# ----------------------------------------------------------------------------

def test_cluster_counters_and_warm_start(tmp_path):
    path = tmp_path / "tunes.json"
    a = jnp.ones((256, 256), jnp.float32)
    b = jnp.ones((256, 256), jnp.float32)

    c1 = Cluster(policy=KernelPolicy(mode="tuned", tuning="timed"),
                 tune_db=str(path))
    assert c1.tune_db_warm == 0
    with use_policy(c1._policy):
        ops.tuned_call("matmul", a, b)      # miss -> race
        ops.tuned_call("matmul", a, b)      # registry hit
    st = c1._policy.stats
    assert st["tune_misses"] == 1 and st["tune_races"] == 1
    assert st["tune_hits"] == 1
    assert len(c1.tune_db) == 1

    # "second process": registry cold, same DB -> warm start, zero races
    registry.KERNEL_TUNES.clear()
    tunedb.set_active_db(None)
    c2 = Cluster(policy=KernelPolicy(mode="tuned", tuning="timed"),
                 tune_db=str(path))
    assert c2.tune_db_warm == 1
    with use_policy(c2._policy):
        ops.tuned_call("matmul", a, b)
    st2 = c2._policy.stats
    assert st2.get("tune_hits") == 1
    assert "tune_misses" not in st2 and "tune_races" not in st2


def test_program_report_carries_tunedb(tmp_path):
    from repro.cluster import BenchProgram
    path = tmp_path / "tunes.json"
    cluster = Cluster(policy="tuned", tune_db=str(path))
    program = cluster.compile(BenchProgram(sections=("table1",), smoke=True))
    rep = program.report()
    assert rep["tunedb"]["path"] == str(path)
    assert rep["tunedb"]["warm_started"] == 0
    assert rep["policy"]["tuning"] == "auto"


def test_cluster_without_db_has_no_tunedb_report():
    from repro.cluster import BenchProgram
    cluster = Cluster(policy="tuned")
    assert cluster.tune_db is None
    rep = cluster.compile(
        BenchProgram(sections=("table1",), smoke=True)).report()
    assert "tunedb" not in rep


# ----------------------------------------------------------------------------
# the second benchmark run is race-free (the bench's own racing path)
# ----------------------------------------------------------------------------

def test_second_bench_run_zero_races(tmp_path):
    """bench_table1_kernels.tuned_rows twice against one DB: run 1 races
    every kernel, run 2 (cold registry, warm DB) races none — the property
    the CI tune-DB cache exists for."""
    from benchmarks import bench_table1_kernels as b1

    path = tmp_path / "tunes.json"
    db = tunedb.TuneDB(path)
    pol1 = KernelPolicy(mode="tuned", tuning="timed")
    with tunedb.use_db(db), use_policy(pol1):
        rows1 = b1.tuned_rows(smoke=True)
    assert pol1.stats["tune_races"] == len(rows1)
    assert pol1.stats["tune_misses"] == len(rows1)
    for r in rows1:
        assert r["source"] == "timed"
        assert r["us_tuned"] <= r["us_default"] * (1 + 1e-9), r
        assert r["measured_speedup"] >= 1.0

    # fresh process: cold registry, warm DB
    registry.KERNEL_TUNES.clear()
    db2 = tunedb.TuneDB(path)
    assert db2.warm_start(backend=BACKEND, mode="tuned") == len(rows1)
    pol2 = KernelPolicy(mode="tuned", tuning="timed")
    with tunedb.use_db(db2), use_policy(pol2):
        rows2 = b1.tuned_rows(smoke=True)
    assert "tune_races" not in pol2.stats and "tune_misses" not in pol2.stats
    assert pol2.stats["tune_hits"] == len(rows2)
    assert [r["blocks"] for r in rows2] == [r["blocks"] for r in rows1]
    assert db2.stores == 0          # nothing new to write


# ----------------------------------------------------------------------------
# the perf gate
# ----------------------------------------------------------------------------

def _gate_record(tuned_us: float, default_us: float) -> dict:
    return {
        "rows": [
            {"name": "table1_tuned/matmul", "us_per_call": tuned_us,
             "derived": f"default_us={default_us:.1f};blocks=bm=512;"
                        f"measured_speedup=1.50;source=timed;p_local=0.9"},
            {"name": "table1_fused/rmsnorm_matmul", "us_per_call": 100.0,
             "derived": "unfused_us=150.0;bytes_reduction=2.5"},
        ],
        "decode": [
            {"name": "decode/K1", "us_per_call": 1000.0,
             "derived": "tokens_per_s=1500.0;stall_pct=0.2;host_syncs=32"},
            {"name": "decode/K16", "us_per_call": 500.0,
             "derived": "tokens_per_s=3800.0;stall_pct=0.5;host_syncs=2"},
        ],
        "serve_continuous": [
            {"name": "serve/continuous", "us_per_call": 180.0,
             "derived": "tokens_per_s=5400.0;occupancy_pct=79.0;p99_ms=90"},
            {"name": "serve/static", "us_per_call": 340.0,
             "derived": "tokens_per_s=2900.0;occupancy_pct=45.0;p99_ms=180"},
        ],
    }


def _run_gate(tmp_path, record, baseline=None, require="tuned", tol=0.15):
    from benchmarks import check_gate
    bench = tmp_path / "bench.json"
    bench.write_text(json.dumps(record))
    argv = ["--bench", str(bench), "--require", require, "--tol", str(tol)]
    if baseline is not None:
        base = tmp_path / "baseline.json"
        base.write_text(json.dumps(baseline))
        argv += ["--baseline", str(base)]
    return check_gate.main(argv)


def test_gate_passes_when_tuned_not_slower(tmp_path):
    assert _run_gate(tmp_path, _gate_record(90.0, 100.0),
                     require="tuned,fused,decode,serve") == 0


def test_gate_fails_when_tuned_slower(tmp_path):
    assert _run_gate(tmp_path, _gate_record(130.0, 100.0)) == 1


def test_gate_tolerance_absorbs_timer_noise(tmp_path):
    assert _run_gate(tmp_path, _gate_record(110.0, 100.0), tol=0.15) == 0
    assert _run_gate(tmp_path, _gate_record(110.0, 100.0), tol=0.05) == 1


def test_gate_fails_on_missing_sections(tmp_path):
    record = _gate_record(90.0, 100.0)
    del record["serve_continuous"]
    assert _run_gate(tmp_path, record,
                     require="tuned,fused,decode,serve") == 1


def test_gate_baseline_regressions(tmp_path):
    good = _gate_record(90.0, 100.0)
    # stall regression beyond tolerance fails
    worse = json.loads(json.dumps(good))
    worse["decode"][1]["derived"] = \
        "tokens_per_s=3800.0;stall_pct=9.5;host_syncs=2"
    assert _run_gate(tmp_path, worse, baseline=good) == 1
    # occupancy collapse fails
    worse2 = json.loads(json.dumps(good))
    worse2["serve_continuous"][0]["derived"] = \
        "tokens_per_s=5400.0;occupancy_pct=40.0;p99_ms=90"
    assert _run_gate(tmp_path, worse2, baseline=good) == 1
    # within tolerance passes
    assert _run_gate(tmp_path, good, baseline=good) == 0


def test_gate_paged_requirement(tmp_path):
    record = _gate_record(90.0, 100.0)
    # rows absent -> fail
    assert _run_gate(tmp_path, record, require="paged") == 1
    record["serve_continuous"] += [
        {"name": "serve/paged_kv", "us_per_call": 170.0,
         "derived": "tokens_per_s=5900.0;private_tokens_per_s=4300.0;"
                    "capacity_x=1.75;pages_shared=24;cow_forks=2;"
                    "pool_exhausted=0"},
        {"name": "serve/prefix_reuse", "us_per_call": 1400.0,
         "derived": "cold_ttft_p50_ms=4.3;warm_ttft_p50_ms=1.4;"
                    "ttft_speedup_x=3.1;prefill_skipped=96;prefix_hits=8"},
    ]
    assert _run_gate(tmp_path, record, require="paged") == 0
    # prefix reuse never fired -> fail (no skip, no speedup)
    bad = json.loads(json.dumps(record))
    bad["serve_continuous"][-1]["derived"] = (
        "cold_ttft_p50_ms=4.3;warm_ttft_p50_ms=4.2;ttft_speedup_x=1.0;"
        "prefill_skipped=0;prefix_hits=0")
    assert _run_gate(tmp_path, bad, require="paged") == 1
    # no capacity win at equal memory -> fail
    bad2 = json.loads(json.dumps(record))
    bad2["serve_continuous"][-2]["derived"] = (
        "tokens_per_s=5900.0;capacity_x=1.00")
    assert _run_gate(tmp_path, bad2, require="paged") == 1
