"""bench.py survivability: the driver records the TAIL of stdout, so
whatever kills the process, the last line must be a parseable record
(round 4 lost its entire scorecard to rc=124 with empty output) — and
the exit code says whether that record is a measurement: non-zero when
no TPU came up, when any unit carries an error, and when a signal cut
the run short.  These tests take the explicit CPU route
(GEOMX_BENCH_PLATFORM=cpu); the default mode never falls back to it."""

import json
import os
import signal
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_bench_tail_parses_under_sigterm(tmp_path):
    """Default-tier on purpose despite being a subprocess test: it
    guards the round's scorecard artifact, and on CPU it completes in
    ~15s (spawn + one tiny config + SIGTERM handshake).  The bench's
    own watchdog budgets are pinned low so a wedged bench bounds this
    test instead of hanging it."""
    env = dict(os.environ)
    env.update({
        "GEOMX_BENCH_PLATFORM": "cpu",
        "GEOMX_BENCH_BATCH": "32",
        "GEOMX_BENCH_ITERS": "1",
        "GEOMX_BENCH_TTA": "0",
        "GEOMX_BENCH_INIT_TIMEOUT": "60",
        "GEOMX_BENCH_INIT_ATTEMPTS": "1",
        "GEOMX_BENCH_TIMEOUT": "90",
        # the copy below runs from tmp_path: the package comes from here
        "PYTHONPATH": REPO,
    })
    env.pop("XLA_FLAGS", None)
    # run a uniquely-named copy: the bench child re-execs its own file
    # path, so this name identifies parent AND child in pgrep without
    # false-matching unrelated processes that mention "bench.py"
    script = tmp_path / f"bench_under_test_{os.getpid()}.py"
    with open(os.path.join(REPO, "bench.py")) as f:
        script.write_text(f.read())
    proc = subprocess.Popen(
        [sys.executable, str(script)], cwd=REPO, env=env,
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True)
    lines = []
    try:
        # the startup snapshot arrives within seconds of spawn; read
        # until the first config lands so the kill hits mid-measurement.
        # A pump thread makes the deadline real: a wedged bench emitting
        # nothing must FAIL this test, not block readline() forever
        import queue
        import threading

        q: "queue.Queue" = queue.Queue()

        def _pump():
            for ln in iter(proc.stdout.readline, ""):
                q.put(ln)
            q.put(None)

        threading.Thread(target=_pump, daemon=True).start()
        deadline = time.time() + 150
        saw_config = False
        while time.time() < deadline:
            try:
                line = q.get(timeout=max(0.1, deadline - time.time()))
            except queue.Empty:
                break
            if line is None:
                break
            lines.append(line.strip())
            try:
                snap = json.loads(lines[-1])
            except json.JSONDecodeError:
                continue
            assert snap.get("partial") is True  # pre-final snapshots
            if snap.get("configs"):
                saw_config = True
                break
        assert saw_config, f"no config completed within 150s: {lines[-3:]}"
        proc.send_signal(signal.SIGTERM)
        # a run cut short is a failed run: 128 + SIGTERM, never 0
        assert proc.wait(timeout=30) == 128 + signal.SIGTERM
        # drain what the handler wrote on its way out (pump thread owns
        # the pipe; it posts None at EOF)
        while True:
            try:
                line = q.get(timeout=5)
            except queue.Empty:
                break
            if line is None:
                break
            if line.strip():
                lines.append(line.strip())
    finally:
        if proc.poll() is None:
            proc.kill()

    tail = json.loads(lines[-1])  # MUST parse — this is the contract
    assert "signal 15" in (tail.get("error") or "")
    assert tail["configs"], tail
    assert tail["metric"].startswith("resnet20")
    # and the handler reaped the measurement child — an orphan would
    # wedge the chip for the next process (round-4 failure mode)
    time.sleep(1.0)
    out = subprocess.run(
        ["pgrep", "-f", script.name], capture_output=True, text=True)
    assert out.returncode != 0, f"orphan bench child: {out.stdout}"


def test_bench_resume_child_recovers_failed_unit(tmp_path):
    """A TPU runtime crash mid-measurement takes down every later phase
    in the SAME child (r5 extras run: configs OK, then microbench /
    profile / sweep all UNAVAILABLE).  The parent must respawn one
    fresh child that skips the units it already holds good results for
    and re-runs the failed ones — the final record ends clean."""
    env = dict(os.environ)
    env.update({
        "GEOMX_BENCH_PLATFORM": "cpu",
        "GEOMX_BENCH_BATCH": "16",
        "GEOMX_BENCH_ITERS": "1",
        "GEOMX_BENCH_TTA": "0",
        "GEOMX_BENCH_INIT_TIMEOUT": "60",
        "GEOMX_BENCH_INIT_ATTEMPTS": "1",
        "GEOMX_BENCH_TIMEOUT": "240",
        # fires in the first child only: the config errors there, then
        # the resume child (GEOMX_BENCH_DONE non-empty) measures it
        "GEOMX_BENCH_FAULT_UNIT": "config:bsc",
        # two configs keep both children cheap; the semantics under
        # test (skip-good / re-run-failed) are config-count-independent
        "GEOMX_BENCH_CONFIGS": "vanilla_local,bsc",
    })
    env.pop("XLA_FLAGS", None)
    env.pop("GEOMX_BENCH_DONE", None)
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")], cwd=REPO,
        env=env, capture_output=True, text=True, timeout=540)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [ln for ln in out.stdout.splitlines() if ln.strip()]
    tail = json.loads(lines[-1])
    # the faulted unit was re-measured clean by the resume child
    assert "error" not in tail["configs"]["bsc"], tail["configs"]["bsc"]
    assert tail["configs"]["bsc"]["samples_per_sec_per_chip"] > 0
    assert "partial" not in tail and tail.get("error") is None
    # both the original attempt and the resume are on the record
    attempts = [a["attempt"] for a in tail["init_attempts"]]
    assert attempts == [1, "resume1"], attempts
    # the injected failure itself was visible in an intermediate
    # snapshot — the resume must IMPROVE the record, not mask history
    saw_fault = any(
        "injected fault" in json.dumps(json.loads(ln).get(
            "configs", {}).get("bsc", {}))
        for ln in lines if ln.startswith("{"))
    assert saw_fault, "first child's config error never surfaced"


def test_resume_clears_error_only_when_all_units_good():
    """ADVICE r5 #4: a clean resume attempt must NOT reset the top-level
    error while some recorded unit still carries a per-unit failure —
    the headline would say success over a failing scorecard."""
    sys.path.insert(0, REPO)
    try:
        import bench
    finally:
        sys.path.pop(0)
    good = {"samples_per_sec_per_chip": 1.0}
    results = {"configs": {"a": dict(good), "b": {"error": "boom"}},
               "backend": {}, "fit_loop": None, "microbench": None,
               "profile": None, "batch_sweep": None, "tta": None,
               "tta_s2d": None}
    # clean resume, but config "b" still failed -> keep the error
    assert not bench._resume_clears_error(results, True, None)
    # the failed unit recovers -> now the error may clear
    results["configs"]["b"] = dict(good)
    assert bench._resume_clears_error(results, True, None)
    # a resume that itself failed never clears, even with good units
    assert not bench._resume_clears_error(results, True, "watchdog")
    assert not bench._resume_clears_error(results, False, None)
    # a failed resumable phase (e.g. tta) also blocks the clear
    results["tta"] = {"error": "died"}
    assert not bench._resume_clears_error(results, True, None)


def test_compare_zero_watchdog_publishes_phase_forensics():
    """The main bench's watchdog applied to the micro-modes: a wedged
    --compare-zero run must publish the same forensic bundle the main
    bench's watchdog does — the hung phase by name, the per-phase
    timestamp trail, and the child's faulthandler stacks — instead of
    burning the budget silently."""
    env = dict(os.environ)
    env.update({
        "GEOMX_BENCH_TIMEOUT": "4",
        # wedge the child right after its first phase mark, before the
        # jax import, so the test bounds at ~10s
        "GEOMX_BENCH_FAULT_HANG_INIT": "120",
    })
    env.pop("GEOMX_BENCH_COMPARE_CHILD", None)
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"),
         "--compare-zero", "--model=mlp"], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=90)
    lines = [ln for ln in out.stdout.splitlines() if ln.startswith("{")]
    assert lines, out.stderr[-2000:]
    rec = json.loads(lines[-1])
    assert rec["mode"] == "compare_zero"
    assert rec.get("ok") is not True
    assert "watchdog" in rec, rec.get("error")
    wd = rec["watchdog"]
    assert wd["phase"] == "child_start"
    assert "child_start" in wd["init_phases"]
    assert "backend_up" not in wd["init_phases"]
    stacks = "\n".join(wd["stacks"])
    assert "time.sleep" in stacks or "File" in stacks, stacks[:500]
    assert "watchdog" in rec["error"]


def test_watchdog_publishes_stacks_and_init_phases(tmp_path):
    """Watchdog diagnosability (a record that says only "backend init
    exceeded 480s" gives zero clue where it hung): when the init
    watchdog fires, the published record must carry the per-phase init
    timestamps and the child's all-thread faulthandler stack dump."""
    env = dict(os.environ)
    env.update({
        "GEOMX_BENCH_PLATFORM": "cpu",
        "GEOMX_BENCH_INIT_TIMEOUT": "5",
        "GEOMX_BENCH_INIT_ATTEMPTS": "1",
        "GEOMX_BENCH_RESUME_ATTEMPTS": "0",
        # the hook wedges the child right after its first phase mark,
        # before the jax import, so the whole test bounds at ~10s
        "GEOMX_BENCH_FAULT_HANG_INIT": "120",
    })
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")], cwd=REPO,
        env=env, capture_output=True, text=True, timeout=90)
    # a backend that never came up fails the run; nothing else is tried
    assert out.returncode == 1
    lines = [ln for ln in out.stdout.splitlines() if ln.startswith("{")]
    assert lines, out.stderr[-2000:]
    rec = json.loads(lines[-1])
    assert "watchdog" in rec, rec.get("error")
    assert [a["attempt"] for a in rec["init_attempts"]] == [1]
    assert "degraded" not in rec and "captured_evidence" not in rec
    wd = rec["watchdog"]
    assert wd["phase"] == "backend init"
    # the child got as far as its first phase mark — and no further
    assert "child_start" in wd["init_phases"]
    assert "jax_imported" not in wd["init_phases"]
    assert rec["init_phases"]["child_start"] is not None
    # the SIGUSR1 faulthandler dump reached the record: real stack
    # lines naming the wedged frame
    stacks = "\n".join(wd["stacks"])
    assert "Thread" in stacks or "File" in stacks, stacks[:500]
    assert "time.sleep" in stacks or "bench" in stacks, stacks[:500]
    assert "last init phase: child_start" in rec["error"]


def test_default_mode_without_a_chip_fails_and_measures_nothing():
    """No fallback: with no TPU (this sandbox holds JAX to the CPU) the
    default mode exits non-zero, and its record carries no device, no
    config and no value — a CPU number is never written under a device
    metric's name.  Every attempt is the same environment in a fresh
    process: nothing scrubbed, nothing switched off."""
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "GEOMX_BENCH_INIT_TIMEOUT": "120",
        "GEOMX_BENCH_INIT_ATTEMPTS": "1",
    })
    for k in ("GEOMX_BENCH_PLATFORM", "XLA_FLAGS"):
        env.pop(k, None)
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")], cwd=REPO,
        env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 1, out.stdout[-2000:]
    lines = [ln for ln in out.stdout.splitlines() if ln.startswith("{")]
    rec = json.loads(lines[-1])
    assert "not a TPU" in rec["error"]
    assert rec["device"] is None and rec["configs"] == {}
    assert rec["value"] == 0.0 and rec["mfu"] is None
    assert "degraded" not in rec
    assert [a["attempt"] for a in rec["init_attempts"]] == [1]
    assert "retry_env" not in rec["init_attempts"][0]


def test_failed_unit_fails_the_run():
    """Any unit of the final record carrying an ``error`` makes the
    exit code non-zero — the piece of parent_main's contract the
    subprocess tests above exercise end to end."""
    sys.path.insert(0, REPO)
    try:
        import bench
    finally:
        sys.path.pop(0)
    good = {"samples_per_sec_per_chip": 1.0}
    results = {"configs": {"a": dict(good)}, "backend": {},
               "fit_loop": None, "microbench": None, "profile": None,
               "batch_sweep": None, "tta": None, "tta_s2d": None}
    assert not bench._has_failures(results, None)
    assert bench._has_failures(results, "watchdog: measurement exceeded")
    results["configs"]["b"] = {"error": "boom"}
    assert bench._has_failures(results, None)
    results["configs"]["b"] = dict(good)
    results["fit_loop"] = {"error": "died"}
    assert bench._has_failures(results, None)
