"""Distributed request tracing across the serving fleet (ISSUE 18).

The load-bearing contracts:

- **header codec is junk-proof** — ``X-FM-Trace`` comes from an
  untrusted peer; malformed/oversized values parse to None, never an
  exception in the replica's request path;
- **keep-alive dispatch** — the fleet parent parks replica
  connections and reuses them (``dispatch_reused_connection_total``
  counts the wins); a stale parked socket costs ONE retry on a fresh
  dial, not a failed request;
- **torn input renders, never crashes** — trace_report skips junk
  JSONL lines and flags a trace whose dispatch erred or whose replica
  hops are missing (the SIGKILL'd-replica shape) as INCOMPLETE;
- **clock skew is corrected** — replica spans are laid on the
  parent's timeline via the NTP-style dispatch/handle estimate, so a
  5-second replica clock error doesn't become a 5-second "hop";
- **the acceptance drill** — a real ``--fleet 2`` CLI run under
  loadgen (with a mid-request replica kill and a byte-torn span file)
  merges into traces with >= 4 hops across >= 3 PIDs, the p99
  exemplar's trace_id resolves to a full merged trace, and
  run_doctor names the dominant hop of the slowest trace.
"""

import importlib.util
import json
import os
import queue
import signal
import socket
import subprocess
import sys
import threading
import time
import http.client
import http.server

import jax
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from fm_spark_tpu import models, obs  # noqa: E402
from fm_spark_tpu.obs.trace import TraceContext  # noqa: E402
from fm_spark_tpu.resilience import faults  # noqa: E402
from fm_spark_tpu.serve import loadgen  # noqa: E402
from fm_spark_tpu.serve import fleet as fleet_mod  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_tool(name: str):
    path = os.path.join(REPO, "tools", f"{name}.py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


# ------------------------------------------------------- context codec


def test_trace_context_header_round_trip():
    ctx = TraceContext("abc123")
    assert ctx.to_header() == "abc123;"
    rt = TraceContext.from_header(ctx.to_header())
    assert rt.trace_id == "abc123" and rt.parent_span_id is None

    rt = TraceContext.from_header("abc123;dead-beef")
    assert rt.trace_id == "abc123"
    assert rt.parent_span_id == "dead-beef"
    assert TraceContext.from_header(rt.to_header()).parent_span_id == \
        "dead-beef"


def test_trace_context_rejects_junk():
    # None/empty/malformed/oversized/wrong-typed header values all
    # parse to None — the replica must never 500 on a hostile header.
    for junk in (None, "", ";", "  ;  ", "bad$id;x", ";orphan-parent",
                 "a" * 200 + ";x", 42, 3.14, b"x;y", ["x"]):
        assert TraceContext.from_header(junk) is None, junk
    # A bad PARENT token is dropped but the trace id survives: half a
    # link beats a torn trace.
    rt = TraceContext.from_header("abc123;bad$parent")
    assert rt.trace_id == "abc123" and rt.parent_span_id is None


def test_trace_context_child_links_downstream():
    ctx = TraceContext("t1")
    child = ctx.child("aaa-1")
    assert child is not ctx
    assert child.trace_id == "t1" and child.parent_span_id == "aaa-1"
    # span_id None (tracing disabled at this hop): the chain degrades
    # to the upstream parent rather than breaking.
    assert ctx.child(None) is ctx


def test_mint_trace_sampling_and_disabled_path(tmp_path):
    obs.shutdown(reason=None)
    # Unconfigured process: no trace, no urandom cost (the <=1% bound
    # in test_obs_overhead rides this early-out).
    assert obs.mint_trace() is None
    assert obs.mint_trace(sample=1.0) is None
    obs.configure(str(tmp_path / "run"), run_id="mint",
                  install_signals=False)
    try:
        minted = {obs.mint_trace().trace_id for _ in range(8)}
        assert len(minted) == 8, "trace ids must be unique"
        assert all(TraceContext.from_header(f"{t};") for t in minted)
        # sample=0.0 keeps nothing; deterministic, not probabilistic.
        assert all(obs.mint_trace(sample=0.0) is None
                   for _ in range(32))
    finally:
        obs.shutdown(reason=None)


# -------------------------------------------------- exemplars + rollup


def test_histogram_exemplars_tail_buckets_remember_traces():
    from fm_spark_tpu.obs.metrics import MetricsRegistry

    reg = MetricsRegistry()
    h = reg.histogram("req_ms", buckets=(1.0, 10.0))
    h.observe(0.5)                        # untagged: bucket stays bare
    h.observe(5.0, exemplar="t-mid")
    h.observe(9999.0, exemplar="t-tail")
    h.observe(8888.0, exemplar="t-tail2")  # LAST in the bucket wins
    ex = h.exemplars()
    assert "1" not in ex
    assert ex["10"] == {"value": 5.0, "trace_id": "t-mid"}
    assert ex["+Inf"] == {"value": 8888.0, "trace_id": "t-tail2"}
    assert h.summary()["exemplars"] == ex

    # OpenMetrics exposition carries the exemplar suffix — the
    # trace_id a Grafana panel shows next to the p99 line.
    text = reg.prometheus_text()
    assert 'trace_id="t-tail2"' in text
    assert " # {" in text

    # bucket_snapshot is the raw form the fleet rollup ships.
    snap = reg.bucket_snapshot()
    assert snap["req_ms"]["exemplars"] == ex
    assert snap["req_ms"]["counts"] == [1, 1, 2]


def test_render_fleet_metrics_labels_and_bucket_sums():
    from fm_spark_tpu.obs.export import render_fleet_metrics

    assert render_fleet_metrics(None) == ""
    assert render_fleet_metrics({"replicas": {}}) == ""

    def rep(requests, counts, count, total):
        return {
            "pid": 1,
            "snapshot": {"counters": {"serve.requests_total": requests},
                         "gauges": {"engine.depth": 1.5}},
            "buckets": {"serve/request_ms": {
                "bounds": [1.0, 10.0], "counts": counts,
                "count": count, "sum": total, "exemplars": {}}},
        }

    text = render_fleet_metrics({"replicas": {
        0: rep(5, [1, 2, 3], 6, 42.0),
        1: rep(7, [0, 1, 1], 2, 8.0),
        2: "not a dict — a half-scraped replica must not break /metrics",
    }})
    assert 'fm_spark_fleet_serve_requests_total{replica="0"} 5' in text
    assert 'fm_spark_fleet_serve_requests_total{replica="1"} 7' in text
    assert 'fm_spark_fleet_engine_depth{replica="0"} 1.5' in text
    # One TYPE line per metric, not per replica.
    assert text.count(
        "# TYPE fm_spark_fleet_serve_requests_total counter") == 1
    # Histogram aggregate: raw bucket counts summed element-wise,
    # re-exposed cumulatively ([1,3,4] -> 1, 4, +Inf 8).
    assert 'fm_spark_fleet_serve_request_ms_bucket{le="1"} 1' in text
    assert 'fm_spark_fleet_serve_request_ms_bucket{le="10"} 4' in text
    assert 'fm_spark_fleet_serve_request_ms_bucket{le="+Inf"} 8' in text
    assert "fm_spark_fleet_serve_request_ms_count 8" in text
    assert "fm_spark_fleet_serve_request_ms_sum 50" in text


# ------------------------------------------------- keep-alive dispatch


class _ReplicaStub(http.server.BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"

    def do_POST(self):
        self.rfile.read(int(self.headers.get("Content-Length") or 0))
        self.server.trace_headers.append(
            self.headers.get(obs.TRACE_HEADER))
        body = json.dumps({"ok": True}).encode()
        self.send_response(200)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def log_message(self, *args):
        pass


def test_dispatch_keepalive_reuses_and_survives_stale_socket():
    srv = http.server.ThreadingHTTPServer(("127.0.0.1", 0), _ReplicaStub)
    srv.trace_headers = []
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    port = srv.server_address[1]
    pool = fleet_mod.ConnectionPool("127.0.0.1", port)
    ctr = obs.counter("fleet.dispatch_reused_connection_total")
    c0 = ctr.value
    try:
        st, _doc = fleet_mod._http_json(
            "127.0.0.1", port, "POST", "/predict", body={"x": 1},
            pool=pool, trace=TraceContext("tid1", "par-1"))
        assert st == 200
        assert ctr.value == c0, "first dispatch dials fresh"

        st, _doc = fleet_mod._http_json(
            "127.0.0.1", port, "POST", "/predict", body={"x": 2},
            pool=pool, trace=TraceContext("tid1", "par-2"))
        assert st == 200
        assert ctr.value == c0 + 1, "second dispatch rides the parked socket"
        # Both hops carried the context header (what the fmlint
        # trace-propagation rule pins statically).
        assert srv.trace_headers == ["tid1;par-1", "tid1;par-2"]

        # A replica that died between dispatches leaves a dead parked
        # socket: park one wired to a peer that's already gone and the
        # next dispatch must retry ONCE on a fresh dial and succeed.
        lst = socket.create_server(("127.0.0.1", 0))
        stale = http.client.HTTPConnection("127.0.0.1", port)
        stale.sock = socket.create_connection(lst.getsockname())
        peer, _addr = lst.accept()
        peer.close()
        lst.close()
        pool.give(stale)
        st, doc = fleet_mod._http_json(
            "127.0.0.1", port, "POST", "/predict", body={"x": 3},
            pool=pool)
        assert st == 200 and doc == {"ok": True}
        assert ctr.value == c0 + 1, "the stale-retry dial is not a reuse"
    finally:
        pool.close()
        srv.shutdown()
        srv.server_close()


def test_connection_pool_bounds_idle():
    pool = fleet_mod.ConnectionPool("127.0.0.1", 1, max_idle=2)
    conns = [pool.fresh() for _ in range(3)]
    for c in conns:
        pool.give(c)              # third one is closed, not parked
    assert len(pool._idle) == 2
    c, reused = pool.take()
    assert reused and c is conns[1], "LIFO: hottest socket first"
    pool.close()
    assert pool.take()[1] is False, "closed pool still dials fresh"


# --------------------------------------------------- trace_report unit

CLIENT_PID, PARENT_PID, REPLICA_PID = 0xCCC, 0xAAA, 0xBBB
#: Replica wall clock runs 5 s ahead of the parent's in the synthetic
#: fixture; the NTP-style estimate must recover exactly this.
SKEW_S = 5.0


def _span(pid, seq, name, trace, t_start, dur_ms, **attrs):
    return {"event": "span", "name": name,
            "span_id": f"{pid:x}-{seq:x}", "t_start": t_start,
            "dur_ms": dur_ms, "trace": trace, **attrs}


def _write_jsonl(path, docs, tail=b""):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "wb") as f:
        for d in docs:
            f.write((json.dumps(d) + "\n").encode())
        f.write(tail)


def _synthetic_root(tmp_path) -> str:
    """Three per-process run dirs under one obs root: a complete trace
    ``aaa111`` (replica clock +5 s skewed) and a torn trace ``bbb222``
    whose replica died mid-request (dispatch erred, replica hops never
    written)."""
    root = str(tmp_path / "obs")
    _write_jsonl(os.path.join(root, "client", "trace.jsonl"), [
        _span(CLIENT_PID, 1, "client/request", "aaa111", 99.99, 130.0),
        {"event": "metric", "name": "noise"},       # non-span: ignored
        _span(CLIENT_PID, 2, "client/request", None, 99.0, 1.0),
    ])
    _write_jsonl(os.path.join(root, "parent", "trace.jsonl"), [
        _span(PARENT_PID, 1, "frontdoor/admit", "aaa111", 99.995, 1.0),
        _span(PARENT_PID, 2, "frontdoor/request", "aaa111", 100.0,
              120.0),
        _span(PARENT_PID, 3, "fleet/dispatch", "aaa111", 100.01, 100.0,
              replica=0),
        # Trace bbb222: the replica was killed mid-handle. Its spans
        # never hit disk; the parent's dispatch carries the error.
        _span(PARENT_PID, 4, "frontdoor/admit", "bbb222", 200.0, 1.0),
        _span(PARENT_PID, 5, "frontdoor/request", "bbb222", 200.0,
              50.0),
        _span(PARENT_PID, 6, "fleet/dispatch", "bbb222", 200.001, 49.0,
              replica=1, error="RemoteDisconnected"),
    ])
    # The replica's file ends in a torn line AND raw junk — the shape
    # a SIGKILL leaves behind. Both must be skipped, not fatal.
    _write_jsonl(
        os.path.join(root, "replica", "trace.jsonl"),
        [_span(REPLICA_PID, 1, "replica/handle", "aaa111",
               100.03 + SKEW_S, 60.0,
               remote_parent=f"{PARENT_PID:x}-3"),
         _span(REPLICA_PID, 2, "serve/coalesce", "aaa111",
               100.04 + SKEW_S, 40.0, queue_ms=5.0, exec_ms=30.0,
               split_ms=2.0)],
        tail=b'{"event": "span", "name": "replica/ha\nnot json at all\n')
    _write_jsonl(os.path.join(root, "parent", "metrics.jsonl"), [
        {"histograms": {"frontdoor/request_ms": {"exemplars": {
            "+Inf": {"value": 10.0, "trace_id": "stale-snapshot"}}}}},
        {"histograms": {"frontdoor/request_ms": {"exemplars": {
            "100": {"value": 42.0, "trace_id": "bbb222"},
            "+Inf": {"value": 120.0, "trace_id": "aaa111"}}}}},
    ])
    return root


def test_trace_report_merges_and_corrects_skew(tmp_path):
    tr = _load_tool("trace_report")
    root = _synthetic_root(tmp_path)

    skew = tr.estimate_skew(tr.collect(root))
    assert skew[(PARENT_PID, REPLICA_PID)] == pytest.approx(SKEW_S,
                                                            abs=1e-6)

    merged = tr.merge(root)
    assert set(merged) == {"aaa111", "bbb222"}

    full = merged["aaa111"]
    assert full["hops"] == 6
    assert full["pids"] == sorted([PARENT_PID, REPLICA_PID, CLIENT_PID])
    assert not full["incomplete"]
    # Uncorrected, the skewed replica spans would stretch this to ~5 s;
    # corrected, the client's round trip bounds the trace.
    assert full["total_ms"] == pytest.approx(130.0, abs=0.01)

    bd = tr.breakdown(full)
    assert bd["client"] == 130.0
    assert bd["admission"] == 1.0
    assert bd["frontdoor"] == pytest.approx(20.0)   # request - dispatch
    assert bd["transport"] == pytest.approx(40.0)   # dispatch - handle
    assert bd["replica"] == pytest.approx(20.0)     # handle - coalesce
    assert (bd["coalesce_wait"], bd["execute"], bd["split"]) == \
        (5.0, 30.0, 2.0)
    assert bd["dominant"] == "transport"


def test_trace_report_flags_torn_trace_and_resolves_exemplar(tmp_path):
    tr = _load_tool("trace_report")
    root = _synthetic_root(tmp_path)
    merged = tr.merge(root)

    torn = merged["bbb222"]
    assert torn["incomplete"]
    assert torn["error_hops"] == ["fleet/dispatch"]
    assert set(torn["missing"]) == {"replica/handle", "serve/coalesce"}

    ex = tr.tail_exemplar(root)
    assert ex == {"trace_id": "aaa111", "value": 120.0, "le": "+Inf"}

    out = tr.render_trace(merged["aaa111"])
    assert "<-- dominant" in out and "dispatch transport" in out
    out = tr.render_trace(torn)
    assert "INCOMPLETE" in out and "fleet/dispatch (error)" in out
    assert "(missing)" in out

    full = tr.render(merged, root=root)
    assert "tail exemplar: trace aaa111" in full
    assert "resolves to a merged trace" in full
    assert "1 trace(s) incomplete" in full


def test_trace_report_cli(tmp_path, capsys):
    tr = _load_tool("trace_report")
    root = _synthetic_root(tmp_path)
    assert tr.main([root]) == 0
    out = capsys.readouterr().out
    assert "# Request traces (2 merged)" in out
    assert tr.main([root, "--trace", "bbb222"]) == 0
    assert "INCOMPLETE" in capsys.readouterr().out
    assert tr.main([root, "--trace", "nope"]) == 1
    assert tr.main([str(tmp_path / "missing")]) == 2


def test_run_doctor_tracing_section_on_synthetic_root(tmp_path):
    doctor = _load_tool("run_doctor")
    root = _synthetic_root(tmp_path)
    tracing = doctor.tracing_diagnose(os.path.join(root, "parent"))
    assert tracing["n_traces"] == 2 and tracing["incomplete"] == 1
    assert tracing["top"][0]["trace_id"] == "aaa111"
    assert tracing["top"][0]["dominant"] == "transport"
    assert tracing["exemplar"]["resolved"] is True

    notes = doctor.tracing_findings(tracing)
    joined = "\n".join(notes)
    assert "dominant hop transport" in joined
    assert "1 of 2 trace(s) INCOMPLETE" in joined

    # An exemplar pointing at a trace nobody's span file holds is a
    # finding, not a pass: the writer died before its first flush.
    tracing["exemplar"] = {"trace_id": "ghost", "value": 1.0,
                           "le": "+Inf", "resolved": False}
    assert any("does NOT resolve" in n
               for n in doctor.tracing_findings(tracing))


# ---------------------------------------- the fleet acceptance drill


def _drain(stream, sink: "queue.Queue[str]"):
    for line in iter(stream.readline, ""):
        sink.put(line)
    sink.put("")


def _next_doc(sink, key, deadline_s, proc, stderr_path):
    """The next stdout JSON line carrying ``key``, within a budget."""
    t_end = time.monotonic() + deadline_s
    while True:
        left = t_end - time.monotonic()
        if left <= 0 or proc.poll() is not None:
            with open(stderr_path, errors="replace") as f:
                err = f.read()[-4000:]
            raise AssertionError(
                f"no {key!r} line from the serve process "
                f"(rc={proc.poll()}); stderr tail:\n{err}")
        try:
            line = sink.get(timeout=min(left, 1.0))
        except queue.Empty:
            continue
        try:
            doc = json.loads(line)
        except ValueError:
            continue
        if isinstance(doc, dict) and key in doc:
            return doc


def test_fleet_tracing_end_to_end(tmp_path):
    """ISSUE 18 acceptance: a ``--fleet 2`` CLI run under loadgen —
    with a replica SIGKILL'd mid-request and a span file torn at the
    byte level afterwards — must still merge into at least one trace
    with >= 4 hops spanning >= 3 PIDs (client, front-door parent,
    replica), flag the killed request's trace INCOMPLETE, resolve the
    p99 exemplar's trace_id to a full merged trace, count reused
    dispatch connections, and show up in run_doctor with a dominant
    hop."""
    spec = models.FieldFMSpec(num_features=4 * 64, rank=4, num_fields=4,
                              bucket=64, init_std=0.1)
    model_dir = str(tmp_path / "model")
    models.save_model(model_dir, spec, spec.init(jax.random.key(0)))
    obs_root = str(tmp_path / "obs")

    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache"),
           # The kill plan rides the environment into the REPLICAS
           # (the parent never arms the replica_kill point): the 4th
           # handled request across the fleet dies mid-flight.
           faults.ENV_PLAN: "replica_kill@4=exit:9",
           faults.ENV_STATE: str(tmp_path / "fault_state.json")}
    stderr_path = str(tmp_path / "serve.stderr")
    proc = subprocess.Popen(
        [sys.executable, "-m", "fm_spark_tpu.cli", "serve",
         "--fleet", "2", "--model", model_dir, "--buckets", "1,4",
         "--obs-dir", obs_root, "--frontdoor-port", "0",
         "--trace-sample", "1.0", "--latency-budget-ms", "0",
         "--reload-poll-s", "0"],
        stdout=subprocess.PIPE, stderr=open(stderr_path, "w"),
        text=True, cwd=REPO, env=env)
    sink: "queue.Queue[str]" = queue.Queue()
    threading.Thread(target=_drain, args=(proc.stdout, sink),
                     daemon=True).start()
    run_id = None
    try:
        run_id = _next_doc(sink, "run_id", 60, proc,
                           stderr_path)["run_id"]
        door = _next_doc(sink, "frontdoor", 300, proc,
                         stderr_path)["frontdoor"]
        host, port = door["url"].split("//", 1)[1].split(":")

        # The loadgen runs IN THIS PROCESS with its own obs run dir
        # under the same root — its client/request spans are the
        # trace's third PID.
        obs.shutdown(reason=None)
        obs.configure(os.path.join(obs_root, "client0"),
                      run_id="client0", install_signals=False)
        try:
            sched = loadgen.make_schedule(
                "flash_crowd", 5, duration_s=0.6, base_rps=30.0,
                rows=2, deadline_ms=8000.0)
            assert sched.n_requests > 4  # the kill fires mid-burst
            summary = loadgen.run_loadgen(
                host, int(port), sched, str(tmp_path / "tap.jsonl"),
                nnz=spec.num_fields, num_features=spec.num_features,
                threads=4, attempt_timeout_s=60.0)
            assert summary["by_outcome"].get("ok", 0) > 4, summary
        finally:
            obs.shutdown(reason="loadgen done")

        proc.send_signal(signal.SIGTERM)
        assert proc.wait(timeout=120) == 0
    finally:
        proc.kill()
        proc.wait(timeout=30)

    # The torn-file drill on REAL output: rip the tail of one replica
    # span file mid-record. The merge must shrug, not crash.
    replica_traces = [
        os.path.join(obs_root, d, "trace.jsonl")
        for d in os.listdir(obs_root)
        if d not in (run_id, "client0")
        and os.path.exists(os.path.join(obs_root, d, "trace.jsonl"))]
    assert replica_traces, "replicas wrote no span files"
    with open(replica_traces[0], "ab") as f:
        f.write(b'{"event": "span", "name": "replica/hand')

    tr = _load_tool("trace_report")
    merged = tr.merge(obs_root)
    assert merged, "no traces merged from the fleet run"

    # >= 4 hops across >= 3 processes, including THIS process (the
    # client) and the CLI parent (front door + fleet).
    full = [t for t in merged.values()
            if t["hops"] >= 4 and len(t["pids"]) >= 3]
    assert full, {tid: (t["hops"], t["pids"])
                  for tid, t in merged.items()}
    assert any(os.getpid() in t["pids"] and proc.pid in t["pids"]
               for t in full)
    # Every trace names a dominant hop.
    assert all(tr.breakdown(t)["dominant"] for t in full)

    # The killed request's trace survives INCOMPLETE (errored dispatch
    # hop and/or replica hops that never hit the dead replica's file).
    assert any(t["incomplete"] for t in merged.values()), \
        "replica_kill left no incomplete trace"

    # The p99 exemplar resolves to one concrete, fully-merged trace.
    ex = tr.tail_exemplar(obs_root)
    assert ex is not None, "front door exported no exemplars"
    assert ex["trace_id"] in merged
    assert merged[ex["trace_id"]]["hops"] >= 4

    # Keep-alive dispatch earned reuses on the real fleet path.
    with open(os.path.join(obs_root, run_id, "metrics.jsonl"),
              errors="replace") as f:
        last = [json.loads(ln) for ln in f if ln.strip()][-1]
    assert last["counters"].get(
        "fleet.dispatch_reused_connection_total", 0) >= 1

    # run_doctor stitches it into the diagnosis: section + dominant
    # hop of the slowest trace.
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "run_doctor.py"),
         "--run-id", run_id, obs_root],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=REPO)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "Request tracing" in out.stdout
    assert "dominant hop" in out.stdout
