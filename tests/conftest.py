"""Test harness: force an 8-device virtual CPU mesh BEFORE jax initializes.

Mirrors how the reference simulates multi-node MPI on a single host by
listing localhost with many slots (fed_launch/README.md:11-27) — here the
"nodes" are virtual XLA CPU devices so sharding/collective code paths run
for real without TPU hardware.
"""

import os

# Tests run on the virtual 8-device CPU mesh whatever the machine holds:
# JAX_PLATFORMS in the environment is all the installed JAX needs.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

from fedml_tpu.utils.compile_cache import (  # noqa: E402
    count_cache_events, enable_compile_cache,
)

# Persistent XLA compilation cache: the suite re-instantiates identical models
# across API objects and test files (each instance re-traces, so the in-memory
# jit cache never shares), and compilation dominates the tier-1 wall clock.
# Keyed by HLO hash, so a hit returns the same executable — numerics are
# unaffected. Same helper, same directory rule as every entry point.
_cache_dir = enable_compile_cache()

# Compile-cache observability (fedscope): count the XLA persistent-cache
# hit/miss events jax publishes through jax.monitoring, so the session can
# end with a one-line summary — a cold cache (or a config change that
# silently re-keys every program) shows up as a miss storm in the tier-1
# log instead of as an unexplained budget blowout. tools/t1_report.py
# parses these lines back out of the tee'd log.
_CACHE_EVENTS = count_cache_events()

#: wall seconds per test FILE (setup+call+teardown summed over its tests);
#: printed as one machine-parseable line for tools/t1_report.py
_FILE_SECONDS: dict = {}


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running (excluded from the tier-1 gate)")
    config.addinivalue_line(
        "markers",
        "chaos: seeded wire-fault injection (comm/chaos.py); small enough "
        "to stay inside the tier-1 time budget — tools/chaos_sweep.py runs "
        "the wide multi-seed version")


def pytest_runtest_logreport(report):
    path = report.nodeid.split("::", 1)[0]
    _FILE_SECONDS[path] = _FILE_SECONDS.get(path, 0.0) + (
        getattr(report, "duration", 0.0) or 0.0)


def pytest_sessionfinish(session, exitstatus):
    import json

    try:
        entries_txt = str(len(os.listdir(_cache_dir)))
    except OSError:      # nothing compiled long enough to be written yet
        entries_txt = "n/a"
    tw = getattr(session.config, "get_terminal_writer", lambda: None)()
    emit = tw.line if tw is not None else print
    # the writer sits mid-line after the last progress dot; break first so
    # the [t1] text can never glue onto a dots line (the tier-1 gate counts
    # dots with a ^...$ regex — a suffixed line would drop out of the count)
    emit("")
    emit(
        f"[t1] compile-cache: {_CACHE_EVENTS['hits']} hit(s) / "
        f"{_CACHE_EVENTS['misses']} miss(es) this session, "
        f"{entries_txt} persistent entries in {os.path.basename(_cache_dir)}")
    slowest = sorted(_FILE_SECONDS.items(), key=lambda kv: -kv[1])[:10]
    emit("[t1] file-seconds: " + json.dumps(
        [[p, round(s, 1)] for p, s in slowest]))
    # fedlint gate digest: run the full analyzer (all rules, fedrace
    # included) over the real tree once per session so the tier-1 log
    # itself records the lint state — a nonzero unsuppressed count here is
    # the same regression test_fedml_tpu_tree_zero_unsuppressed_findings
    # fails on, surfaced even when that test file was deselected
    try:
        from fedml_tpu.analysis import RULES, run_lint

        res = run_lint(os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "fedml_tpu"))
        emit(f"[t1] fedlint: {len(RULES)} rules / {len(res.findings)} "
             f"unsuppressed finding(s), {len(res.suppressed)} suppressed")
    except Exception:
        pass
    # fedpulse session digest: one line when any test streamed a pulse —
    # a silent drop of pulse coverage (or an unexpected critical health
    # event inside the suite) shows up in the tier-1 log itself
    try:
        from fedml_tpu.obs.live import session_stats

        st = session_stats()
        if st["snapshots"]:
            emit(f"[t1] pulse: {st['snapshots']} snapshot(s) over "
                 f"{st['runs']} run(s), {st['critical']} critical health "
                 f"event(s), last {st['last_path']}")
        # fedsketch overhead budget: the pinned 10k-cohort plane-on/off
        # test records its measured wall delta via live.record_overhead;
        # surfacing it per session makes an overhead creep visible in the
        # tier-1 log before it ever trips the 5% pin
        if st.get("overhead_pct") is not None:
            emit(f"[t1] obs-overhead: {st['overhead_pct']:+.2f}% wall, "
                 f"full plane on vs off (budget "
                 f"{st['overhead_budget_pct']:g}%)")
    except Exception:
        pass
    # fedlens session digest: one line when any test folded a learning
    # round — a silent drop of lens coverage (the bit-identity, parity
    # and attribution tests all fold) shows up in the tier-1 log itself
    try:
        from fedml_tpu.obs.lens import session_stats as lens_stats

        st = lens_stats()
        if st["folds"]:
            emit(f"[t1] lens: {st['folds']} learning fold(s), "
                 f"{st['clients']} client observation(s), "
                 f"{st['suspects']} suspect(s) ranked this session")
    except Exception:
        pass
    # fedflight session digest: always emitted — a green run expects 0
    # incident bundles from tests that did not mean to trigger one (the
    # flight tests use tmp_path recorders and DO count here; their
    # expected dumps are part of the number, so a drift either way is a
    # behavior change worth seeing in the tier-1 log)
    try:
        from fedml_tpu.obs.flight import session_stats as flight_stats

        st = flight_stats()
        emit(f"[t1] incidents: {st['incidents']} bundle(s) dumped this "
             f"session" + (f", last {st['last_bundle']}"
                           if st["last_bundle"] else ""))
    except Exception:
        pass
