"""bench.py artifact robustness (ISSUE 4 satellite): an unreachable
backend must yield a FAST, explicit JSON error line and a failing exit
code — never an rc:124 with an empty stdout — and the wall-budget
machinery that guards the stream probe / secondary bench must actually
degrade to errors instead of hanging."""
import json
import os
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_wall_budget_degrades_to_timeout(tmp_path):
    sys.path.insert(0, REPO)
    try:
        import bench
    finally:
        sys.path.remove(REPO)
    # point the handler's flight dump at tmp_path — with no dump dir
    # configured it falls back to the system temp dir BY DESIGN (a
    # bare hung run must still leave its who-was-waiting artifact),
    # but repeated test runs must not litter /tmp
    from paddle_tpu.core.flags import FLAGS
    old = FLAGS.telemetry_dump_dir
    FLAGS.telemetry_dump_dir = str(tmp_path)
    t0 = time.time()
    try:
        with pytest.raises(TimeoutError, match="wall budget"):
            with bench._wall_budget(1, "probe"):
                time.sleep(30)
    finally:
        FLAGS.telemetry_dump_dir = old
    assert time.time() - t0 < 5
    # and the alarm is cancelled afterwards
    with bench._wall_budget(1, "ok"):
        pass
    time.sleep(1.2)


def test_layout_bench_artifact_fields():
    """ISSUE 5: a BENCH_LAYOUT=NHWC run's headline JSON must be a
    self-describing experiment — data_format, fused_stages and xla_flags
    fields present — and the emit-immediately contract must hold (the
    partial line carries them too).  Tiny depth-8 model keeps the CPU
    compile fast."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_LAYOUT="NHWC",
               BENCH_DEPTH="8", BENCH_BATCH="4", BENCH_ITERS="2",
               BENCH_FAKE="1", BENCH_LIVENESS_TIMEOUT="30",
               BENCH_SECONDARY="0", BENCH_STREAM_PROBE="0")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(l) for l in proc.stdout.splitlines()
             if l.strip().startswith("{")]
    assert len(lines) >= 2, proc.stdout
    partial, final = lines[0], lines[-1]
    assert partial.get("partial") is True
    for rec in (partial, final):
        assert rec["data_format"] == "NHWC", rec
        assert rec["fused_stages"] > 0, rec
        assert "xla_flags" in rec, rec
        assert rec["depth"] == 8, rec
    assert final["value"] > 0
    # ISSUE 6 satellite: per-step percentiles, sourced from the
    # telemetry histogram, ride the BENCH JSON (p50 <= p90 <= p99)
    for rec in (partial, final):
        assert rec["step_ms_p50"] > 0, rec
        assert rec["step_ms_p50"] <= rec["step_ms_p90"] \
            <= rec["step_ms_p99"], rec


def test_dead_backend_yields_fast_json_error_line(tmp_path):
    """Simulated unreachable backend: bench.py exits in seconds with a
    valid JSON line carrying an explicit ``error`` field, a non-zero
    exit code (a failed run must not look like a passed one) — and
    (ISSUE 6) a flight-recorder artifact naming what was blocked, so
    the next hang is a diagnosis, not an rc:124."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_FAKE_DEAD="1",
               BENCH_LIVENESS_TIMEOUT="3",
               FLAGS_telemetry_dump_dir=str(tmp_path))
    t0 = time.time()
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    elapsed = time.time() - t0
    assert proc.returncode == 1, proc.stderr[-2000:]
    assert elapsed < 90
    lines = [l for l in proc.stdout.splitlines() if l.strip()]
    assert lines, "no artifact line on stdout"
    rec = json.loads(lines[-1])
    assert "error" in rec and "backend unreachable" in rec["error"]
    assert rec["metric"].endswith("_train")
    # the flight-recorder artifact exists and names the blocked op
    assert "flight_recorder" in rec, rec
    assert os.path.exists(rec["flight_recorder"])
    flight = json.loads(open(rec["flight_recorder"]).read())
    assert flight["reason"] == "backend_unreachable"
    assert flight["blocked"]["op"] == "liveness_probe"
    assert "metrics" in flight


def test_wall_budget_expiry_leaves_flight_artifact(tmp_path):
    """Simulated wall-budget expiry (the BENCH_FAKE_DEAD-style degrade
    path): the SIGALRM handler dumps a flight record BEFORE raising,
    and the TimeoutError names its path."""
    sys.path.insert(0, REPO)
    try:
        import bench
    finally:
        sys.path.remove(REPO)
    from paddle_tpu.core.flags import FLAGS

    old = FLAGS.telemetry_dump_dir
    FLAGS.telemetry_dump_dir = str(tmp_path)
    try:
        with pytest.raises(TimeoutError, match="flight recorder:"):
            with bench._wall_budget(1, "probe"):
                time.sleep(30)
    finally:
        FLAGS.telemetry_dump_dir = old
    import glob
    dumps = glob.glob(str(tmp_path / "flight_*.json"))
    assert dumps, "wall-budget expiry left no flight artifact"
    rec = json.loads(open(dumps[0]).read())
    assert rec["reason"].startswith("wall_budget:")
    assert rec["blocked"]["op"] == "probe"
