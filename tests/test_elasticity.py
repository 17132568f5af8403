"""SLO-driven elasticity (serve/autoscale.py + the streaming/priority
transport): the autoscaler's full decision table on synthetic clocks,
live add/retire of real replicas (at-most-once preserved), chunked
streaming rollouts over a real socket (parity, early first chunk,
disconnect-cancels-compute), priority admission (bulk capped + deferred
while the window is degraded), the SLO fill-counter reset regression, and
supervisor ticks over a dynamically-sized ReplicaSet — all CPU."""

import http.client
import json
import os
import queue as pyqueue
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from distegnn_tpu import obs
from distegnn_tpu.models.fast_egnn import FastEGNN
from distegnn_tpu.obs.metrics import MetricsRegistry
from distegnn_tpu.obs.slo import SLOMonitor
from distegnn_tpu.ops.graph import pad_graphs
from distegnn_tpu.serve import (InferenceEngine, RequestQueue, ServeMetrics,
                                synthetic_graph)
from distegnn_tpu.serve.autoscale import ReplicaAutoscaler
from distegnn_tpu.serve.queue import StreamSink
from distegnn_tpu.serve.registry import ModelRegistry
from distegnn_tpu.serve.replica import Replica, ReplicaSet
from distegnn_tpu.serve.transport import Gateway

pytestmark = pytest.mark.serve


# ---- synthetic fixtures for the decision table ------------------------------

class _FakeReplica:
    def __init__(self, idx):
        self.idx = idx
        self.state = "running"
        self.warmups = []

    def warmup(self, sizes):
        self.warmups.append(list(sizes))


class _FakeRSet:
    def __init__(self, n):
        self.replicas = [_FakeReplica(i) for i in range(n)]
        self.retired = []

    def available(self):
        return sum(r.state == "running" for r in self.replicas)

    def add_replica(self, build_fn, warm_sizes=None):
        r = build_fn(len(self.replicas))
        if warm_sizes:
            r.warmup(warm_sizes)
        self.replicas.append(r)
        return r

    def retire_replica(self, drain_timeout_s=30.0):
        running = [r for r in self.replicas if r.state == "running"]
        if len(running) <= 1 or running[-1] is self.replicas[0]:
            return None
        victim = running[-1]
        self.replicas.remove(victim)
        self.retired.append(victim)
        return victim


class _FakeEntry:
    def __init__(self, n=1, depth=0, warmed=()):
        self.replicas = _FakeRSet(n)
        self.queue = SimpleNamespace(depth=lambda: depth)
        self.warmed = [SimpleNamespace(n=w, e=8 * w) for w in warmed]
        self.replica_factory = _FakeReplica

    def add_replica(self, warm_sizes=None):
        # mirrors ModelEntry.add_replica's surface (the swap-lock re-pin
        # has no fake equivalent: there is no engine to version)
        if self.replica_factory is None:
            raise RuntimeError("no replica factory")
        return self.replicas.add_replica(self.replica_factory,
                                         warm_sizes=warm_sizes)

    def set_depth(self, depth):
        self.queue = SimpleNamespace(depth=lambda: depth)


class _FakeRegistry:
    def __init__(self, **entries):
        self.entries = entries

    def items(self):
        return self.entries.items()


class _FakeMonitor:
    def __init__(self, **snap):
        self.snap = snap

    def window_snapshot(self, now=None):
        return dict(self.snap)


@pytest.fixture()
def scale_events(monkeypatch):
    """Record the autoscaler's obs events without a tracer round-trip."""
    from distegnn_tpu.serve import autoscale as mod

    events = []

    def record(name, **attrs):
        if name.startswith("gateway/scale_"):
            events.append(dict(attrs, name=name))

    monkeypatch.setattr(mod.obs, "event", record)
    return events


def _scaler(registry, monitor=None, **knobs):
    cfg = dict(enable=True, min_replicas=1, max_replicas=3, step=1,
               queue_high=4.0, queue_low=0.5, shed_high=0.01,
               scale_up_cooldown_s=2.0, scale_down_cooldown_s=5.0,
               idle_rounds=2)
    cfg.update(knobs)
    return ReplicaAutoscaler(registry, monitor, config=cfg,
                             metrics_registry=MetricsRegistry())


# ---- autoscaler decision table ----------------------------------------------

def test_scale_up_on_queue_depth_then_cooldown_then_max(scale_events):
    entry = _FakeEntry(n=1, depth=30, warmed=(20,))
    sc = _scaler(_FakeRegistry(m=entry))
    sc.tick(now=0.0)
    assert len(entry.replicas.replicas) == 2
    assert scale_events[-1]["name"] == "gateway/scale_up"
    assert scale_events[-1]["triggers"] == ["queue_depth"]
    assert (scale_events[-1]["from_replicas"],
            scale_events[-1]["to_replicas"]) == (1, 2)
    # the new replica was warmed at the entry's warmed rungs
    assert entry.replicas.replicas[-1].warmups == [[(20, 160)]]

    sc.tick(now=0.5)                      # inside the up-cooldown
    assert len(entry.replicas.replicas) == 2
    assert scale_events[-1]["name"] == "gateway/scale_blocked"
    assert (scale_events[-1]["direction"],
            scale_events[-1]["reason"]) == ("up", "cooldown")

    sc.tick(now=3.0)                      # cooldown elapsed: grow again
    assert len(entry.replicas.replicas) == 3
    sc.tick(now=6.0)                      # at max_replicas: blocked
    assert len(entry.replicas.replicas) == 3
    assert scale_events[-1]["reason"] == "max_replicas"
    # triggering gauge values ride every event
    assert scale_events[-1]["depth"] == 30
    assert "per_replica_depth" in scale_events[-1]


def test_scale_up_on_shed_rate_and_p99_triggers(scale_events):
    entry = _FakeEntry(n=1, depth=0)
    sc = _scaler(_FakeRegistry(m=entry),
                 _FakeMonitor(shed_rate=0.2, predict_p99_ms=900.0),
                 p99_high_ms=500.0)
    sc.tick(now=0.0)
    assert scale_events[-1]["name"] == "gateway/scale_up"
    assert scale_events[-1]["triggers"] == ["shed_rate", "p99"]
    assert scale_events[-1]["shed_rate"] == 0.2
    assert scale_events[-1]["predict_p99_ms"] == 900.0


def test_scale_down_after_idle_rounds_with_cooldown(scale_events):
    entry = _FakeEntry(n=3, depth=0)
    sc = _scaler(_FakeRegistry(m=entry), idle_rounds=2,
                 scale_down_cooldown_s=5.0)
    sc.tick(now=0.0)                      # calm 1: nothing yet
    assert len(entry.replicas.replicas) == 3 and not scale_events
    sc.tick(now=1.0)                      # calm 2: retire one
    assert len(entry.replicas.replicas) == 2
    assert scale_events[-1]["name"] == "gateway/scale_down"
    assert (scale_events[-1]["from_replicas"],
            scale_events[-1]["to_replicas"]) == (3, 2)
    sc.tick(now=2.0)                      # calm 1 again (reset on action)
    sc.tick(now=3.0)                      # calm 2 but inside down-cooldown
    assert len(entry.replicas.replicas) == 2
    assert scale_events[-1]["name"] == "gateway/scale_blocked"
    assert (scale_events[-1]["direction"],
            scale_events[-1]["reason"]) == ("down", "cooldown")
    sc.tick(now=7.0)                      # cooldown elapsed: down to min
    assert len(entry.replicas.replicas) == 1
    sc.tick(now=20.0)                     # at min_replicas: no event, no-op
    assert len(entry.replicas.replicas) == 1
    assert scale_events[-1]["name"] == "gateway/scale_down"


def test_busy_tick_resets_calm_streak(scale_events):
    entry = _FakeEntry(n=2, depth=0)
    sc = _scaler(_FakeRegistry(m=entry), idle_rounds=2)
    sc.tick(now=0.0)                      # calm 1
    entry.set_depth(2)                    # not calm (>= queue_low), no trigger
    sc.tick(now=1.0)
    entry.set_depth(0)
    sc.tick(now=2.0)                      # calm 1 again — streak restarted
    assert len(entry.replicas.replicas) == 2
    sc.tick(now=3.0)                      # calm 2: now it retires
    assert len(entry.replicas.replicas) == 1


def test_scale_up_blocked_without_factory_and_on_spawn_failure(scale_events):
    entry = _FakeEntry(n=1, depth=10)
    entry.replica_factory = None
    sc = _scaler(_FakeRegistry(m=entry))
    sc.tick(now=0.0)
    assert scale_events[-1]["reason"] == "no_factory"
    assert len(entry.replicas.replicas) == 1

    def boom(idx):
        raise RuntimeError("no capacity")

    entry.replica_factory = boom
    sc.tick(now=10.0)
    assert scale_events[-1]["reason"] == "spawn_failed"
    assert "no capacity" in scale_events[-1]["error"]
    assert len(entry.replicas.replicas) == 1


def test_disabled_autoscaler_start_is_noop():
    sc = ReplicaAutoscaler(_FakeRegistry(), config={"enable": False})
    assert sc.start()._thread is None
    sc.stop()                             # idempotent on a never-started loop


def test_status_reports_fleet_shape():
    entry = _FakeEntry(n=2, depth=0)
    sc = _scaler(_FakeRegistry(m=entry), max_replicas=4)
    sc.tick(now=0.0)
    st = sc.status()["m"]
    assert st["replicas"] == 2 and st["available"] == 2
    assert st["min"] == 1 and st["max"] == 4
    assert st["calm_rounds"] == 1


# ---- live fleet: real replicas ----------------------------------------------

@pytest.fixture(scope="module")
def tiny():
    model = FastEGNN(node_feat_nf=1, edge_attr_nf=2, hidden_nf=16,
                     virtual_channels=2, n_layers=2)
    graph = synthetic_graph(24, seed=5)
    tight = pad_graphs([graph], node_bucket=1, edge_bucket=1)
    params = model.init(jax.random.PRNGKey(0), tight)
    x, _ = model.apply(params, tight)
    return SimpleNamespace(model=model, graph=graph, params=params,
                           ref=np.asarray(x[0]))


def _mk_rset(tiny, n, name="m", **q_kw):
    metrics = ServeMetrics()
    kw = dict(batch_deadline_ms=2.0, queue_capacity=32,
              request_timeout_ms=30_000.0, result_margin_s=30.0)
    kw.update(q_kw)
    pairs = []
    for _ in range(n):
        eng = InferenceEngine(tiny.model, tiny.params, max_batch=2,
                              metrics=metrics,
                              rollout_opts={"radius": 0.35, "max_degree": 64,
                                            "max_per_cell": 64})
        pairs.append((eng, RequestQueue(eng, metrics=metrics, **kw)))
    return ReplicaSet(name, pairs,
                      supervisor_opts=dict(heartbeat_s=3600.0))


def _factory(tiny, metrics):
    def build(idx):
        eng = InferenceEngine(tiny.model, tiny.params, max_batch=2,
                              metrics=metrics)
        return Replica(idx, eng, RequestQueue(
            eng, metrics=metrics, batch_deadline_ms=2.0,
            request_timeout_ms=30_000.0, result_margin_s=30.0))
    return build


def test_add_then_retire_replica_live(tiny):
    """A 1 -> 2 -> 1 fleet cycle under live traffic: the added replica
    serves identical numbers, retirement drains before removal, replica 0
    is never the victim, and indices never alias across the cycle."""
    rset = _mk_rset(tiny, 1).start()
    try:
        added = rset.add_replica(_factory(tiny, rset.metrics))
        assert added.idx == 1 and len(rset.replicas) == 2
        futs = [rset.submit(dict(tiny.graph)) for _ in range(6)]
        for f in futs:
            np.testing.assert_allclose(f.result(timeout=60.0), tiny.ref,
                                       atol=1e-4, rtol=0)
        assert {f.meta["replica"] for f in futs} == {0, 1}

        victim = rset.retire_replica(drain_timeout_s=10.0)
        assert victim is added and victim.state == "stopped"
        assert [r.idx for r in rset.replicas] == [0]
        assert rset.retire_replica() is None      # floor: last replica stays
        # the next grow gets a FRESH index — no gauge/health aliasing
        again = rset.add_replica(_factory(tiny, rset.metrics))
        assert again.idx == 2
        assert rset.submit(dict(tiny.graph)).result(timeout=60.0).shape \
            == (24, 3)
    finally:
        rset.stop()


def test_retire_waits_for_inflight_then_fails_over_stragglers(tiny):
    """Scale-down vs in-flight: a wedged victim's tracked request is NOT
    lost — after the bounded drain it fails over to the survivor exactly
    once (the same claim protocol as the supervisor's)."""
    rset = _mk_rset(tiny, 2).start()
    try:
        victim = rset.replicas[1]
        victim.queue.wedge(2.0)           # park the dispatcher mid-flight
        futs = [rset.submit(dict(tiny.graph)) for _ in range(2)]
        assert victim.inflight_count() >= 1
        out = rset.retire_replica(drain_timeout_s=0.2)
        assert out is victim
        for f in futs:
            np.testing.assert_allclose(f.result(timeout=60.0), tiny.ref,
                                       atol=1e-4, rtol=0)
        assert len(rset.replicas) == 1
    finally:
        rset.stop()


def test_supervisor_ticks_dynamic_membership(tiny):
    """Satellite: the supervisor's tick iterates the LIVE list — a replica
    added mid-breaker is supervised immediately with its own counters, the
    set can shrink while another member's breaker is open, and after
    begin_stop() no tick revives a dead queue."""
    rset = _mk_rset(tiny, 2).start()
    sup = rset.supervisor
    try:
        # break replica 1: three crash/restart cycles open its breaker
        bad = rset.replicas[1]
        t = 100.0
        while bad.state != "broken":
            bad.queue.kill(reason="chaos")
            sup.tick(now=t)               # crash noticed
            if bad.state == "broken":
                break
            assert bad.state == "backoff"
            sup.tick(now=t + 60.0)        # backoff elapsed: fresh queue
            assert bad.state == "running"
            t += 100.0
        assert bad.failures == sup.breaker_threshold

        # grow while the breaker is open: the newcomer is supervised from
        # the very next tick, with no registration step and NO index or
        # failure-count aliasing against the broken member
        added = rset.add_replica(_factory(tiny, rset.metrics))
        assert added.idx == 2
        added.queue.kill(reason="chaos")
        sup.tick(now=t + 1.0)
        assert added.state == "backoff" and added.failures == 1
        assert bad.state == "broken"      # untouched by the newcomer's crash

        # both restart (bad goes half-open after its cooldown)
        sup.tick(now=t + 61.0)
        assert added.state == "running" and bad.state == "running"

        # shrink while serving: retire never picks replica 0, membership
        # shrinks mid-supervision, and the next tick walks the new list
        victim = rset.retire_replica(drain_timeout_s=5.0)
        assert victim is added
        assert [r.idx for r in rset.replicas] == [0, 1]
        sup.tick(now=t + 62.0)            # no stale-index touch, no throw

        # begin_stop(): a replica downed with a due restart stays down —
        # drain must never revive a queue
        bad.queue.kill(reason="chaos")
        sup.tick(now=t + 63.0)
        assert bad.state in ("backoff", "broken")
        rset.begin_stop()
        sup.tick(now=t + 10_000.0)
        assert not bad.queue.alive()
        assert bad.state != "running"
    finally:
        rset.stop()


# ---- streaming over the ReplicaSet ------------------------------------------

def test_streamed_rollout_chunks_match_buffered(tiny):
    rset = _mk_rset(tiny, 1).start()
    try:
        scene = {"loc": tiny.graph["loc"], "vel": tiny.graph["vel"],
                 "steps": 5, "chunk_steps": 2}
        sink = StreamSink()
        fut = rset.submit_rollout(dict(scene), stream=sink)
        chunks, summary = [], None
        deadline = time.monotonic() + 120.0
        while time.monotonic() < deadline:
            try:
                kind, a, b = sink.next(timeout=0.5)
            except pyqueue.Empty:
                continue
            if kind == "chunk":
                chunks.append((a, b))
            elif kind == "done":
                summary = a
                break
            else:
                raise a
        assert summary is not None and not summary["cancelled"]
        assert [c[0] for c in chunks] == [0, 2, 4]
        assert [c[1].shape[0] for c in chunks] == [2, 2, 1]
        streamed = np.concatenate([c[1] for c in chunks], axis=0)
        buffered = rset.submit_rollout(
            {"loc": tiny.graph["loc"], "vel": tiny.graph["vel"],
             "steps": 5}).result(timeout=120.0)
        np.testing.assert_allclose(streamed, buffered, atol=1e-5, rtol=0)
        assert fut.result(timeout=10.0)["steps_done"] == 5
    finally:
        rset.stop()


def test_cancelled_stream_skips_remaining_chunks(tiny):
    rset = _mk_rset(tiny, 1).start()
    try:
        sink = StreamSink()
        fut = rset.submit_rollout(
            {"loc": tiny.graph["loc"], "vel": tiny.graph["vel"],
             "steps": 40, "chunk_steps": 2}, stream=sink)
        kind, start, traj = sink.next(timeout=120.0)
        assert kind == "chunk" and start == 0
        sink.cancel()                     # client went away after chunk 1
        summary = fut.result(timeout=120.0)
        assert summary["cancelled"] is True
        assert summary["steps_done"] < summary["steps_total"] == 40
    finally:
        rset.stop()


# ---- the HTTP surface: streaming + priority ---------------------------------

class _Live:
    def __init__(self, **gw_kw):
        self.tiny = None
        model = FastEGNN(node_feat_nf=1, edge_attr_nf=2, hidden_nf=16,
                         virtual_channels=2, n_layers=2)
        self.graph = synthetic_graph(24, seed=5)
        tight = pad_graphs([self.graph], node_bucket=1, edge_bucket=1)
        self.params = model.init(jax.random.PRNGKey(0), tight)
        metrics = ServeMetrics()
        self.engine = InferenceEngine(
            model, self.params, max_batch=2, metrics=metrics,
            rollout_opts={"radius": 0.35, "max_degree": 64,
                          "max_per_cell": 64})
        self.queue = RequestQueue(self.engine, batch_deadline_ms=5.0,
                                  request_timeout_ms=60_000.0,
                                  metrics=metrics)
        self.registry = ModelRegistry.single("nbody", self.engine, self.queue,
                                             feat_nf=1, edge_attr_nf=2)
        self.registry.start()
        self.registry.warmup([24])
        kw = dict(port=0, max_inflight=16,
                  metrics_registry=MetricsRegistry(), stream_chunk_steps=2)
        kw.update(gw_kw)
        self.gw = Gateway(self.registry, **kw)
        self.thread = threading.Thread(target=self.gw.serve_forever,
                                       daemon=True)
        self.thread.start()
        self.url = self.gw.url

    def close(self):
        self.gw.drain()
        self.thread.join(timeout=30.0)
        self.gw.close()


@pytest.fixture(scope="module")
def live():
    env = _Live()
    yield env
    env.close()


def _post(url, payload, headers=None, timeout=120.0):
    hdrs = {"Content-Type": "application/json"}
    hdrs.update(headers or {})
    req = urllib.request.Request(url, data=json.dumps(payload).encode(),
                                 headers=hdrs, method="POST")
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.load(r), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.load(e), dict(e.headers)


def _stream_lines(url, payload, timeout=120.0):
    """POST and read the chunked NDJSON response incrementally, stamping
    each line's arrival time."""
    req = urllib.request.Request(url, data=json.dumps(payload).encode(),
                                 headers={"Content-Type": "application/json"},
                                 method="POST")
    lines = []
    with urllib.request.urlopen(req, timeout=timeout) as r:
        assert r.status == 200
        assert r.headers.get("Content-Type") == "application/x-ndjson"
        while True:
            raw = r.readline()
            if not raw:
                break
            lines.append((time.monotonic(), json.loads(raw)))
    return lines


def test_streamed_rollout_http_parity_and_early_first_chunk(live):
    """?stream=1: NDJSON chunk lines concatenate to the exact buffered
    trajectory, the first line carries only chunk_steps of the total (the
    server answered before finishing), and the summary line closes it."""
    payload = {"positions": live.graph["loc"].tolist(),
               "velocities": live.graph["vel"].tolist(), "steps": 5,
               "chunk_steps": 2}
    lines = _stream_lines(live.url("/v1/models/nbody/rollout?stream=1"),
                          payload)
    body = [ln for _, ln in lines]
    assert body[-1]["done"] is True and body[-1]["cancelled"] is False
    assert body[-1]["steps"] == body[-1]["steps_total"] == 5
    chunks = body[:-1]
    assert [c["start_step"] for c in chunks] == [0, 2, 4]
    assert chunks[0]["steps"] == 2 < 5    # partial answer arrived first
    streamed = np.concatenate(
        [np.asarray(c["chunk"], np.float32) for c in chunks], axis=0)

    status, resp, _ = _post(live.url("/v1/models/nbody/rollout"),
                            {k: v for k, v in payload.items()
                             if k != "chunk_steps"})
    assert status == 200
    np.testing.assert_allclose(streamed,
                               np.asarray(resp["trajectory"], np.float32),
                               atol=1e-5, rtol=0)


def test_non_streaming_rollout_unchanged_by_query_flag(live):
    """stream=0 (and no query) keep the buffered single-JSON contract."""
    payload = {"positions": live.graph["loc"].tolist(), "steps": 2}
    for path in ("/v1/models/nbody/rollout",
                 "/v1/models/nbody/rollout?stream=0"):
        status, resp, _ = _post(live.url(path), payload)
        assert status == 200 and "trajectory" in resp and "done" not in resp


def test_stream_disconnect_cancels_remaining_compute(live, tmp_path):
    """Mid-stream disconnect: the server notices at the next chunk write,
    cancels the rollout (serve/stream_cancelled with steps skipped), and
    the admission slot frees."""
    from distegnn_tpu.obs import report, trace

    trace.configure(log_dir=str(tmp_path))
    try:
        host, port = live.gw.address
        conn = http.client.HTTPConnection(host, port, timeout=60.0)
        body = json.dumps({"positions": live.graph["loc"].tolist(),
                           "steps": 60, "chunk_steps": 2})
        conn.request("POST", "/v1/models/nbody/rollout?stream=1", body=body,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        first = resp.readline()           # one chunk consumed...
        assert json.loads(first)["start_step"] == 0
        conn.sock.close()                 # ...then the client vanishes
        conn.close()

        cancelled = None
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline and cancelled is None:
            trace.flush()
            events = report.load_events(str(tmp_path / "events.jsonl"))[0]
            for e in events:
                if e.get("name") == "serve/stream_cancelled":
                    cancelled = e
            time.sleep(0.1)
    finally:
        trace.configure(log_dir=None)
    assert cancelled is not None, "no serve/stream_cancelled event"
    assert cancelled["steps_total"] == 60
    assert cancelled["steps_skipped"] > 0
    assert cancelled["steps_done"] + cancelled["steps_skipped"] == 60
    # the slot freed: the gateway still serves
    with live.gw._inflight_lock:
        assert live.gw._inflight == 0
    status, resp, _ = _post(live.url("/v1/models/nbody/rollout"),
                            {"positions": live.graph["loc"].tolist(),
                             "steps": 2})
    assert status == 200


# ---- priority admission -----------------------------------------------------

def test_priority_classes_and_header_override(live):
    gw = live.gw
    assert gw._priority_of(SimpleNamespace(headers={}), "predict") \
        == "interactive"
    assert gw._priority_of(SimpleNamespace(headers={}), "rollout") == "bulk"
    h = SimpleNamespace(headers={"X-Priority": "interactive"})
    assert gw._priority_of(h, "rollout") == "interactive"
    h = SimpleNamespace(headers={"X-Priority": "Bulk"})
    assert gw._priority_of(h, "predict") == "bulk"
    h = SimpleNamespace(headers={"X-Priority": "nonsense"})
    assert gw._priority_of(h, "rollout") == "bulk"     # bad value: default


def _wait_drained(gw, timeout_s: float = 30.0) -> None:
    """The module's gateway is shared: a handler thread of an earlier test
    releases its slot just AFTER its client has the response, so the
    counters reach zero a moment later than the test before returns."""
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        with gw._inflight_lock:
            if gw._inflight == 0 and gw._inflight_bulk == 0:
                return
        time.sleep(0.01)
    raise AssertionError(
        f"gateway did not drain in {timeout_s} s: inflight={gw._inflight} "
        f"bulk={gw._inflight_bulk}")


def test_bulk_capped_below_interactive(live):
    """The bulk share of max_inflight is bounded; interactive still admits
    when every bulk slot is taken."""
    gw = live.gw
    cap = gw.bulk_max_inflight
    assert cap < gw.max_inflight
    _wait_drained(gw)
    taken = 0
    try:
        for _ in range(cap):
            assert gw._try_acquire("bulk")
            taken += 1
        assert not gw._try_acquire("bulk")            # bulk share exhausted
        assert gw._try_acquire("interactive")         # interactive admits
        gw._release("interactive")
    finally:
        for _ in range(taken):
            gw._release("bulk")
    with gw._inflight_lock:
        assert gw._inflight == 0 and gw._inflight_bulk == 0


def test_degraded_window_defers_bulk_not_interactive():
    """When the SLO window degrades past the shed threshold, bulk rollouts
    get 429 BulkDeferred with a class-scaled Retry-After while interactive
    (header-promoted) requests keep flowing."""
    env = _Live(priority={"degrade_shed_rate": 0.05,
                          "bulk_retry_factor": 4.0})
    try:
        # poison the window: 10 sheds out of 10 inference requests
        for _ in range(10):
            env.gw.slo_monitor.observe_http("predict", 1.0, 429)
        env.gw._degraded_cache = (0.0, False)   # force a re-check
        payload = {"positions": env.graph["loc"].tolist(), "steps": 2}
        status, resp, hdrs = _post(env.url("/v1/models/nbody/rollout"),
                                   payload)
        assert status == 429 and resp["type"] == "BulkDeferred"
        assert float(hdrs["Retry-After"]) >= 4.0    # 1.0 * factor
        assert resp["priority"] == "bulk"
        # the same request promoted to interactive is served
        status, resp, _ = _post(env.url("/v1/models/nbody/rollout"),
                                payload,
                                headers={"X-Priority": "interactive"})
        assert status == 200 and "trajectory" in resp
        # a predict is never deferred by the degrade gate
        status, _, _ = _post(env.url("/v1/models/nbody/predict"),
                             {"positions": env.graph["loc"].tolist(),
                              "radius": 0.8})
        assert status == 200
    finally:
        env.close()


def test_priority_disabled_restores_flat_admission():
    env = _Live(priority={"enable": False, "degrade_shed_rate": 0.0})
    try:
        for _ in range(10):
            env.gw.slo_monitor.observe_http("predict", 1.0, 429)
        env.gw._degraded_cache = (0.0, False)
        status, resp, _ = _post(env.url("/v1/models/nbody/rollout"),
                                {"positions": env.graph["loc"].tolist(),
                                 "steps": 2})
        assert status == 200              # no bulk class, no deferral
    finally:
        env.close()


def test_readyz_reports_autoscale_state():
    env = _Live(autoscale={"enable": True, "interval_s": 3600.0,
                           "max_replicas": 2})
    try:
        with urllib.request.urlopen(env.url("/readyz"), timeout=30.0) as r:
            body = json.load(r)
        assert body["ready"] is True
        st = body["autoscale"]["nbody"]
        assert st["replicas"] == 1 and st["max"] == 2
    finally:
        env.close()


# ---- SLO window regressions -------------------------------------------------

def test_fill_window_survives_counter_reset():
    """Satellite: a replica restart resets the cumulative slot counters;
    the windowed fill gauge must re-baseline instead of going negative."""
    mon = SLOMonitor(window_s=60.0)
    reg = MetricsRegistry()

    class _Metrics:
        def __init__(self, filled, slots):
            self.batch_slots_filled = filled
            self.batch_slots_total = slots

    class _Entry:
        def __init__(self, filled, slots):
            self.queue = SimpleNamespace(depth=lambda: 0)
            self.engine = SimpleNamespace(metrics=_Metrics(filled, slots))

    class _Reg:
        def __init__(self, entry):
            self.entry = entry

        def items(self):
            return [("m", self.entry)]

    e = _Entry(80, 100)
    mon.export(reg, _Reg(e), now=0.0)
    e.engine.metrics = _Metrics(90, 120)
    mon.export(reg, _Reg(e), now=1.0)
    assert reg.gauge("slo/window_model_m_fill").value == pytest.approx(0.5)

    # restart: counters fall back toward zero — the old diff would be
    # negative; the gauge must re-baseline and stay sane
    e.engine.metrics = _Metrics(4, 8)
    mon.export(reg, _Reg(e), now=2.0)
    e.engine.metrics = _Metrics(10, 16)
    mon.export(reg, _Reg(e), now=3.0)
    v = reg.gauge("slo/window_model_m_fill").value
    assert 0.0 <= v <= 1.0
    assert v == pytest.approx(6.0 / 8.0)


def test_window_snapshot_speaks_the_slo_vocabulary():
    mon = SLOMonitor(window_s=60.0)
    for ms, status in ((10.0, 200), (20.0, 200), (30.0, 429), (40.0, 500)):
        mon.observe_http("predict", ms, status, now=1.0)
    mon.observe_http("rollout", 100.0, 200, now=1.0)
    snap = mon.window_snapshot(now=2.0)
    assert snap["window_requests"] == 5.0
    assert snap["predict_p50_ms"] == pytest.approx(10.0)  # nearest-rank
    assert snap["rollout_p99_ms"] == pytest.approx(100.0)
    assert snap["shed_rate"] == pytest.approx(0.2)
    assert snap["error_rate"] == pytest.approx(0.2)
    # everything ages out of the window
    assert mon.window_snapshot(now=120.0)["window_requests"] == 0.0


# ---- config-key lint --------------------------------------------------------

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _key_lint():
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    try:
        from check_config_keys import find_violations
    finally:
        sys.path.pop(0)
    return find_violations


def test_config_key_lint_clean():
    """Tier-1 wiring of scripts/check_config_keys.py: every serve-layer
    control knob ships a typed default AND a validation branch, and the
    autoscaler's in-code fallback knob set matches the config section."""
    violations = _key_lint()()
    assert violations == [], (
        "config schema halves drifted (default without validation, or "
        f"validator without default): {violations}")


def test_config_key_lint_catches_default_without_validation(tmp_path):
    bad = tmp_path / "config.py"
    bad.write_text(
        '_DEFAULTS: dict = {\n'
        '    "serve": {\n'
        '        "autoscale": {"enable": False, "bogus": 1},\n'
        '    },\n'
        '}\n'
        '\n'
        'def validate_config(cfg):\n'
        '    s = cfg.get("serve")\n'
        '    a = s.get("autoscale")\n'
        '    for key in a:\n'
        '        if key not in ("enable",):\n'
        '            raise ValueError(key)\n')
    violations = _key_lint()(config_path=str(bad), autoscale_path=None)
    assert any("bogus" in msg and "no validation branch" in msg
               for _, _, msg in violations), violations
    # the validated key is NOT flagged
    assert not any("autoscale.enable" in msg for _, _, msg in violations)


# ---- the elasticity spike drill ---------------------------------------------

@pytest.mark.slow
def test_spike_drill_autoscaled_fleet(tmp_path):
    """The end-to-end acceptance drill, all on CPU: a spike10x replay with
    execute-latency chaos against a 1-replica fleet with the autoscaler on.
    Interactive p99 holds its (generous) SLO through every phase, the fleet
    grows then shrinks back (scale_up before scale_down on the event
    stream), and zero accepted requests are lost or errored."""
    slo = tmp_path / "slo.yaml"
    slo.write_text("routes:\n"
                   "  predict: {p99_ms: 60000.0}\n"
                   "error_rate_max: 0.0\n")
    # generous per-request timeout: with injected execute latency plus CPU
    # jit compiles the 1s default would 504 legitimate spike traffic
    cfg = tmp_path / "serve.yaml"
    cfg.write_text("serve:\n  request_timeout_ms: 30000.0\n")
    logs = tmp_path / "logs"
    cmd = [
        sys.executable, os.path.join(REPO, "scripts", "traffic_gen.py"),
        "--config_path", str(cfg),
        "--requests", "40", "--rate", "20", "--seed", "7",
        "--mix", "predict=0.8,session=0.2", "--sizes", "24",
        "--profile", "spike10x",
        "--autoscale",
        "max_replicas=2,queue_high=0.5,scale_up_cooldown_s=0.5,"
        "interval_s=0.1,scale_down_cooldown_s=1.0,idle_rounds=3,"
        "queue_low=2",
        "--scale-settle-s", "30",
        "--chaos", "latency@0.0:s=0.12",
        "--slo", str(slo),
        "--obs-dir", str(logs),
    ]
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    r = subprocess.run(cmd, capture_output=True, text=True, cwd=REPO,
                       env=env, timeout=900)
    assert r.returncode == 0, f"stdout={r.stdout}\nstderr={r.stderr}"
    rec = json.loads(r.stdout.strip().splitlines()[-1])

    # nothing lost, nothing errored — elasticity never sacrificed work
    assert rec["lost"] == 0, rec
    assert rec["errors"] == 0, rec
    assert rec["completed"] == rec["requests"], rec

    # interactive p99 held through every phase, spike included
    assert set(rec["phases"]) == {"pre", "spike", "post"}, rec["phases"]
    for phase, ps in rec["phases"].items():
        assert ps["slo_pass"] is True, (phase, ps)
        assert ps["interactive_p99_ms"] is not None, (phase, ps)

    # the fleet grew under the spike and shrank back before drain
    events = [json.loads(line) for line in
              (logs / "obs" / "events.jsonl").read_text().splitlines()]
    ups = [e for e in events if e.get("name") == "gateway/scale_up"]
    downs = [e for e in events if e.get("name") == "gateway/scale_down"]
    assert ups, "autoscaler never scaled up under a 10x spike"
    assert downs, "autoscaler never scaled back down after the spike"
    assert min(e["ts"] for e in ups) < max(e["ts"] for e in downs)
    assert ups[0]["to_replicas"] > ups[0]["from_replicas"]
    for state in rec["autoscale"].values():
        assert state["replicas"] == state["min"], rec["autoscale"]
