"""Thread-lifecycle audit (ISSUE 15 satellite): every thread the
package starts is daemonized AND joined on its shutdown path, so a
clean close leaves no live package thread behind — pinned by
enumerating threads after shutdown, not by reading the code. Plus the
targeted regression tests for the two genuine thread-safety fixes the
fmlint pass surfaced (watchdog overrun counter, reload follower
outcome counters).
"""

import threading
import time

import pytest

from fm_spark_tpu import obs
from fm_spark_tpu.data.pipeline import Prefetcher
from fm_spark_tpu.obs import export as obs_export
from fm_spark_tpu.resilience.watchdog import WatchdogTable


def _nondaemon_threads():
    return sorted(t.name for t in threading.enumerate()
                  if not t.daemon and t.is_alive())


def _fm_threads():
    return [t for t in threading.enumerate()
            if t.name.startswith("fm-spark") and t.is_alive()]


class _CountSource:
    def __init__(self):
        self.n = 0

    def next_batch(self):
        self.n += 1
        time.sleep(0.001)
        return self.n


def test_static_rule_every_package_thread_daemon_or_joined():
    """The AST half of the audit: the fmlint thread-lifecycle rule is
    clean over the real package — no Thread/Timer without daemon=True
    or a shutdown-path join."""
    from fm_spark_tpu.analysis import core

    found, _ = core.run_rules(core.Context(), rules=["thread-lifecycle"])
    assert found == [], [f.render() for f in found]


def test_no_live_nondaemon_threads_after_clean_shutdown(tmp_path):
    """The runtime half (the satellite's pin): spin up every
    package-owned thread population this suite can construct cheaply —
    prefetcher producer, its placing pool, metrics endpoint, watchdog
    exit-mode monitor —
    drive them, shut them all down cleanly, and enumerate: the
    non-daemon thread set is exactly what it was before, and no
    fm-spark-named thread survives."""
    before = _nondaemon_threads()

    # Prefetcher producer thread.
    pf = Prefetcher(_CountSource(), depth=2)
    assert pf.next_batch() >= 1

    # The placing prefetcher's pool: one worker per device of the mesh
    # (non-daemon executor threads; close() is what joins them).
    import numpy as np

    from fm_spark_tpu.data import Batches
    from fm_spark_tpu.parallel import FieldBatchFeed, make_field_mesh

    rows = np.arange(64 * 3, dtype=np.int32).reshape(64, 3)
    placing = Prefetcher(
        Batches(rows, rows.astype(np.float32), np.ones(64, np.float32), 16),
        depth=2, place=FieldBatchFeed(make_field_mesh(4), 3))
    assert placing.next_batch()[0].shape == (16, 4)
    # (The executor starts a worker only when none is idle: 1 to 4.)
    assert 1 <= len([t for t in _fm_threads()
                     if t.name.startswith("fm-spark-feed")]) <= 4

    # Live-metrics endpoint (ThreadingHTTPServer + serve_forever).
    server = obs_export.start_metrics_server(port=0)
    assert server.port > 0

    # Watchdog exit-mode monitor (armed => monitor thread runs).
    exits = []
    table = WatchdogTable({"step_window": 30.0}, action="exit",
                          _exit=exits.append)
    with table.phase("step_window"):
        pass
    # The obs plane itself (trace sink / flight spool are not threads,
    # but shutdown() is the lifecycle boundary under test).
    obs.configure(str(tmp_path / "obs"), run_id="r-threads")

    pf.close()
    placing.close()
    table.close()
    obs.shutdown()

    deadline = time.monotonic() + 5.0
    while _fm_threads() and time.monotonic() < deadline:
        time.sleep(0.02)
    leftover = _fm_threads()
    assert leftover == [], f"live fm-spark threads after shutdown: " \
                           f"{[t.name for t in leftover]}"
    assert _nondaemon_threads() == before
    assert not pf._thread.is_alive()
    assert not placing._thread.is_alive()
    assert exits == []  # the monitor never fired on a healthy phase


def test_obs_shutdown_stops_the_metrics_endpoint(tmp_path):
    """obs.shutdown() is a shutdown path (ISSUE 15): the endpoint's
    serve_forever thread must not outlive the run — and configure()'s
    internal reason=None replace must NOT kill a server mid-process."""
    obs.configure(str(tmp_path / "a"), run_id="r-a")
    server = obs_export.start_metrics_server(port=0)
    thread = server._thread
    assert thread.is_alive()
    # Re-configure (a new run in the same process): server survives.
    obs.configure(str(tmp_path / "b"), run_id="r-b")
    assert thread.is_alive()
    obs.shutdown()
    thread.join(timeout=5.0)
    assert not thread.is_alive()
    assert obs_export._server is None


def test_watchdog_close_joins_the_monitor_thread():
    table = WatchdogTable({"step_window": 30.0}, action="exit",
                          _exit=lambda rc: None, poll_s=0.01)
    with table.phase("step_window"):
        monitor = table._monitor
        assert monitor is not None and monitor.is_alive()
    table.close()
    assert not monitor.is_alive()
    assert table._monitor is None


# ------------------------------------------------- fix regressions (fmlint)


def test_watchdog_overrun_counter_is_race_safe():
    """Regression for the fmlint thread-lock-discipline finding: the
    exit-mode monitor thread and raise-mode phase exits can note
    overruns concurrently — the counter increment now runs under the
    table lock, so N concurrent notes count exactly N."""
    table = WatchdogTable({"step_window": 1.0}, action="raise")
    n_threads, per_thread = 8, 200
    barrier = threading.Barrier(n_threads)

    def hammer():
        barrier.wait()
        for _ in range(per_thread):
            table._note_overrun("step_window", 1.0, 2.0)

    threads = [threading.Thread(target=hammer, daemon=True)
               for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30.0)
    assert table.hangs_detected == n_threads * per_thread


def test_watchdog_near_miss_counter_is_race_safe():
    """Same defect class, near-miss side (post-review): any thread
    exiting a guarded phase can note a near-miss — the counter and
    the per-phase dump throttle now share the table lock."""
    table = WatchdogTable({"step_window": 1.0}, action="raise")
    n_threads, per_thread = 8, 200
    barrier = threading.Barrier(n_threads)

    def hammer():
        barrier.wait()
        for _ in range(per_thread):
            table._note_near_miss("step_window", 1.0, 0.9)

    threads = [threading.Thread(target=hammer, daemon=True)
               for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30.0)
    assert table.near_misses == n_threads * per_thread


def test_reload_follower_counters_are_race_safe(tmp_path):
    """Regression for the fmlint finding on ReloadFollower.failures:
    a direct poll_once() caller racing the poll loop must not drop
    counts — the counters now increment under a dedicated lock."""
    from fm_spark_tpu.serve.reload import ReloadFollower

    class _Gen:
        shapes = {"w": 1.0}        # the canonical tree the chain restores into
        step = 0

    class _Engine:
        def generation(self):
            return _Gen()

    follower = ReloadFollower(_Engine(), str(tmp_path), poll_s=60.0)
    n_threads, per_thread = 8, 200
    barrier = threading.Barrier(n_threads)

    def hammer():
        barrier.wait()
        for _ in range(per_thread):
            follower._fail("synthetic", target_step=1, served=0)

    threads = [threading.Thread(target=hammer, daemon=True)
               for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30.0)
    assert follower.failures == n_threads * per_thread
    follower.stop()


@pytest.fixture(autouse=True)
def _clean_slate():
    """Never leak a metrics server or obs run into other tests."""
    yield
    obs_export.stop_metrics_server()
    obs.shutdown(reason=None)
