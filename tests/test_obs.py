"""Tier-1 tests for the obs subsystem (distegnn_tpu/obs).

Covers the acceptance surface of the observability PR: span nesting and
timing into JSONL, event round-trip, the obs.enable kill switch (no files,
no-ops), recompile detection through a REAL forced shape change, metrics
primitives + the single nearest-rank percentile implementation, Prometheus
text rendering, the run-report summarize/render/check pipeline, and the
no-bare-print lint (scripts/check_no_print.py) wired into tier-1.

The global tracer is process state; every test that rebinds it goes through
the ``clean_obs`` fixture so it is restored to the sinkless default (and the
compile watcher deactivated) regardless of outcome.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from distegnn_tpu.config import ConfigDict, _DEFAULTS
from distegnn_tpu.obs import jaxprobe, report, trace
from distegnn_tpu.obs.metrics import (
    Counter,
    Gauge,
    LatencyReservoir,
    MetricsRegistry,
    percentile,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def clean_obs():
    """Restore the sinkless global tracer + no active compile watcher after
    a test that configures either."""
    yield
    trace.configure(log_dir=None)
    jaxprobe.deactivate_compile_watcher()


def read_events(path):
    events, bad = report.load_events(path)
    assert bad == 0, f"unparseable lines in {path}"
    return events


# ---- percentile: the single implementation (serve/metrics imports it) ------

@pytest.mark.parametrize("vals", [
    [1.0], [1.0, 2.0], [5.0, 1.0, 4.0, 2.0, 3.0],
    list(float(i) for i in range(100)),
])
@pytest.mark.parametrize("q", [0, 50, 99, 100])
def test_percentile_properties(vals, q):
    s = sorted(vals)
    p = percentile(s, q)
    assert p in s                      # nearest-rank: always a real sample
    assert s[0] <= p <= s[-1]
    if q == 0:
        assert p == s[0]
    if q == 100:
        assert p == s[-1]


def test_percentile_monotone_in_q():
    s = sorted(float(i) for i in range(37))
    ps = [percentile(s, q) for q in (0, 25, 50, 75, 99, 100)]
    assert ps == sorted(ps)


def test_percentile_empty_and_serve_reexport():
    assert percentile([], 50) == 0.0
    assert percentile([], 0) == 0.0
    # serve/metrics re-exports the same function (the old _percentile name)
    from distegnn_tpu.serve.metrics import _percentile
    assert _percentile is percentile


# ---- metrics primitives + registry -----------------------------------------

def test_registry_primitives_and_snapshot():
    reg = MetricsRegistry()
    reg.counter("a/count").add(3)
    reg.counter("a/count").add(2)          # get-or-create: same instance
    reg.gauge("b/depth").set(7)
    r = reg.reservoir("c/lat_ms")
    r.record_many([1.0, 2.0, 3.0, 4.0])
    r.record(5.0)

    snap = reg.snapshot()
    assert snap["a/count"] == 5
    assert snap["b/depth"] == 7
    assert snap["c/lat_ms_count"] == 5
    assert snap["c/lat_ms_sum"] == 15.0
    assert snap["c/lat_ms_p50"] == 3.0
    assert snap["c/lat_ms_p99"] == 5.0
    # snapshot is one JSON object
    assert json.loads(reg.to_json()) == snap


def test_registry_kind_mismatch_raises():
    reg = MetricsRegistry()
    reg.counter("x")
    with pytest.raises(TypeError):
        reg.gauge("x")


def test_reservoir_bounded():
    r = LatencyReservoir(size=10)
    r.record_many([float(i) for i in range(100)])
    assert r.count == 100                  # total ever recorded
    assert len(r.values()) == 10           # reservoir keeps the tail
    assert r.values() == [float(i) for i in range(90, 100)]
    assert r.total == sum(range(100))


def test_render_prometheus_parses():
    reg = MetricsRegistry()
    reg.counter("data/stall_s").add(1.5)
    reg.gauge("queue-depth").set(3)        # '-' must be sanitized
    reg.reservoir("step/ms").record_many([1.0, 2.0, 3.0])
    text = reg.render_prometheus(prefix="distegnn")

    lines = [l for l in text.splitlines() if l]
    types = {}
    for l in lines:
        if l.startswith("# TYPE "):
            _, _, name, kind = l.split()
            types[name] = kind
        else:                              # sample line: name{labels}? value
            name = l.split("{")[0].split()[0]
            val = l.rsplit(" ", 1)[1]
            float(val)                     # every sample value parses
            base = name
            for suf in ("_sum", "_count"):
                if base.endswith(suf) and base[: -len(suf)] in types:
                    base = base[: -len(suf)]
            assert base in types, f"sample {name} missing # TYPE"
            # prometheus-legal metric name
            assert all(c.isalnum() or c in "_:" for c in name)
    assert types["distegnn_data_stall_s"] == "counter"
    assert types["distegnn_queue_depth"] == "gauge"
    assert types["distegnn_step_ms"] == "summary"
    assert 'distegnn_step_ms{quantile="0.50"} 2' in text


# ---- tracer: spans, events, JSONL round-trip -------------------------------

def test_span_nesting_and_timing(tmp_path, clean_obs):
    t = trace.configure(log_dir=str(tmp_path), tags={"run": "t"})
    assert t.enabled
    with t.span("outer", a=1):
        with t.span("inner") as sp:
            sp.set(detail="x")
    t.event("solo", n=3)
    t.flush()

    events = read_events(os.path.join(str(tmp_path), "events.jsonl"))
    assert [e["name"] for e in events] == ["inner", "outer", "solo"]
    inner, outer, solo = events
    assert inner["kind"] == "span" and inner["detail"] == "x"
    assert outer["a"] == 1
    assert 0.0 <= inner["dur_s"] <= outer["dur_s"]  # nested block is shorter
    for e in events:                       # every record carries the tags
        assert e["run"] == "t" and "proc" in e and "host" in e
    assert solo["kind"] == "event" and solo["n"] == 3


def test_span_records_error_and_jsonl_survives_weird_attrs(tmp_path, clean_obs):
    t = trace.configure(log_dir=str(tmp_path))
    with pytest.raises(ValueError):
        with t.span("boom"):
            raise ValueError("x")
    t.event("weird", obj=object(), nan=float("nan"))  # default=repr fallback
    t.flush()
    events = read_events(os.path.join(str(tmp_path), "events.jsonl"))
    assert events[0]["name"] == "boom" and events[0]["error"] == "ValueError"
    assert "object" in events[1]["obj"]


def test_log_is_stdout_compatible_and_mirrored(tmp_path, capsys, clean_obs):
    t = trace.configure(log_dir=str(tmp_path))
    t.log("Epoch 3 ok", epoch=3)
    t.flush()
    # stdout line identical to what the old print produced (process 0)
    assert capsys.readouterr().out == "Epoch 3 ok\n"
    events = read_events(os.path.join(str(tmp_path), "events.jsonl"))
    assert events[0]["kind"] == "log" and events[0]["msg"] == "Epoch 3 ok"
    assert events[0]["epoch"] == 3


def test_disabled_tracer_emits_nothing(tmp_path, capsys, clean_obs):
    """The obs.enable:false kill switch: no files, no events, no watcher;
    log still prints, and spans still reach the ring (they do not depend on
    the sink)."""
    cfg = ConfigDict({**_DEFAULTS, "obs": {**_DEFAULTS["obs"], "enable": False}})
    t = trace.configure_from_config(cfg, str(tmp_path / "exp"))
    assert not t.enabled
    trace.clear_spans()
    with t.span("x", n=1):
        t.event("y")
    t.log("still prints")
    t.flush()
    assert not (tmp_path / "exp").exists()   # not even the directory
    assert capsys.readouterr().out == "still prints\n"
    assert jaxprobe.get_compile_watcher() is None  # probe not installed
    (rec,) = trace.recent_spans()
    assert rec.name == "x" and rec.attrs == {"n": 1} and rec.end_ns >= rec.start_ns


def test_spans_without_a_sink_reach_the_ring(tmp_path, monkeypatch, clean_obs):
    """No configure() at all (the benchmark's drivers): spans are recorded,
    nothing is written anywhere."""
    monkeypatch.chdir(tmp_path)
    trace.configure(log_dir=None)
    trace.clear_spans()
    with trace.span("outer", n=3) as outer:
        with trace.span("inner"):
            pass
    inner, rec = trace.recent_spans()
    assert (inner.name, rec.name) == ("inner", "outer")
    assert inner.parent == rec.id == outer.id and rec.parent == 0
    assert rec.attrs == {"n": 3} and inner.attrs == {}
    assert rec.start_ns <= inner.start_ns <= inner.end_ns <= rec.end_ns
    assert list(tmp_path.iterdir()) == []


def test_ring_is_bounded_and_ordered_across_threads():
    """The ring keeps the newest RING_SIZE records, each thread's in the
    order they ended; parent ids never cross threads."""
    import threading

    trace.clear_spans()
    n = trace.RING_SIZE // 2 + 100
    go = threading.Barrier(2)

    def work(tag):
        go.wait(timeout=10)
        for i in range(n):
            with trace.span("outer/" + tag, i=i):
                with trace.span("inner/" + tag):
                    pass

    threads = [threading.Thread(target=work, args=(t,), name="ring-" + t) for t in "ab"]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
        assert not t.is_alive()
    spans = trace.recent_spans()
    assert len(spans) == trace.RING_SIZE          # 4n records were offered
    by_id = {s.id: s for s in spans}
    assert len(by_id) == len(spans)
    for tag in "ab":
        mine = [s for s in spans if s.thread == "ring-" + tag]
        assert all(s.name.endswith(tag) for s in mine)
        assert [s.end_ns for s in mine] == sorted(s.end_ns for s in mine)
        for s in mine:
            if s.name.startswith("inner"):
                outer = by_id.get(s.parent)       # may have ended after the cut
                assert outer is None or (outer.thread == s.thread
                                         and outer.name == "outer/" + tag)
            else:
                assert s.parent == 0
    assert trace.recent_spans()[-1].attrs == {"i": n - 1}   # the newest survived
    trace.clear_spans()


def test_spanned_decorator_and_error_attr():
    trace.clear_spans()

    @trace.spanned("deco/f")
    def f(x):
        """doc"""
        if x:
            raise KeyError(x)
        return 5

    assert f(0) == 5 and f.__doc__ == "doc"
    with pytest.raises(KeyError):
        f(1)
    ok, bad = trace.recent_spans()
    assert ok.name == bad.name == "deco/f"
    assert "error" not in ok.attrs and bad.attrs["error"] == "KeyError"


def test_configure_from_config_defaults_on(tmp_path, clean_obs):
    cfg = ConfigDict(_DEFAULTS)
    t = trace.configure_from_config(cfg, str(tmp_path / "exp"), tags={"run": "r"})
    assert t.enabled
    assert t.writer.path == str(tmp_path / "exp" / "obs" / "events.jsonl")
    assert jaxprobe.get_compile_watcher() is not None
    t.event("one")
    t.flush()
    assert len(read_events(t.writer.path)) == 1
    # enabled_here=False (train(log=False) test runs) leaves no files either
    t2 = trace.configure_from_config(cfg, str(tmp_path / "exp2"),
                                     enabled_here=False)
    assert not t2.enabled and not (tmp_path / "exp2").exists()


def test_module_level_api_follows_reconfigure(tmp_path, clean_obs):
    from distegnn_tpu import obs
    obs.configure(log_dir=str(tmp_path))
    obs.event("a")
    with obs.span("b"):
        pass
    obs.flush()
    assert [e["name"] for e in
            read_events(str(tmp_path / "events.jsonl"))] == ["a", "b"]


def test_writer_truncates_on_reconfigure(tmp_path, clean_obs):
    trace.configure(log_dir=str(tmp_path))
    trace.event("old")
    trace.flush()
    trace.configure(log_dir=str(tmp_path))   # same dir: fresh stream
    trace.event("new")
    trace.flush()
    events = read_events(str(tmp_path / "events.jsonl"))
    assert [e["name"] for e in events] == ["new"]


# ---- recompile detection (forced shape change) -----------------------------

def test_compile_watcher_detects_forced_recompile(tmp_path, clean_obs):
    import jax
    import jax.numpy as jnp

    t = trace.configure(log_dir=str(tmp_path))
    reg = MetricsRegistry()
    w = jaxprobe.install_compile_watcher(reg)
    w.set_phase("warmup")

    f = jax.jit(lambda x: x * 2 + 1)
    f(jnp.ones((4,))).block_until_ready()
    warm = w.snapshot()
    assert warm["compiles"] >= 1 and warm["compiles_after_warmup"] == 0

    w.set_phase("steady")
    w.mark_warmup_done()
    f(jnp.ones((4,))).block_until_ready()    # cached: no new compile
    assert w.snapshot()["compiles_after_warmup"] == 0

    f(jnp.ones((8,))).block_until_ready()    # forced shape change: recompile
    snap = w.snapshot()
    assert snap["compiles_after_warmup"] >= 1
    assert reg.counter("jax/compiles_after_warmup").value >= 1

    t.flush()
    compiles = [e for e in read_events(str(tmp_path / "events.jsonl"))
                if e["name"] == "jax/compile"]
    assert any(c["after_warmup"] and c["phase"] == "steady" for c in compiles)
    assert all(not c["after_warmup"] for c in compiles
               if c["phase"] == "warmup")


def test_compile_spans_carry_fun_name_and_count_a_second_compile():
    """Every backend compile is a jax/compile span in the ring, watcher or
    not; a forced second compile of one function reads 2 under its name."""
    import jax
    import jax.numpy as jnp

    assert jaxprobe.get_compile_watcher() is None
    trace.clear_spans()

    def twice_compiled(x):
        return x * 3 - 1

    f = jax.jit(twice_compiled)
    with trace.span("caller") as caller:
        f(jnp.ones((5,), jnp.float32)).block_until_ready()
    f(jnp.ones((5,), jnp.float32)).block_until_ready()      # cached: no span
    f(jnp.ones((6,), jnp.float32)).block_until_ready()      # second executable
    mine = [s for s in trace.recent_spans() if s.name == "jax/compile"
            and "twice_compiled" in str(s.attrs.get("fun_name"))]
    assert len(mine) == 2
    assert all(s.end_ns > s.start_ns and "phase" not in s.attrs for s in mine)
    assert mine[0].parent == caller.id and mine[1].parent == 0
    assert all(set(s.attrs) == {"fun_name"} for s in mine)


def test_compile_spans_reach_the_jsonl_only_with_a_watcher(tmp_path, clean_obs):
    """obs.jax_probe: false keeps the file free of jax/compile records; the
    ring has them either way."""
    import jax
    import jax.numpy as jnp

    t = trace.configure(log_dir=str(tmp_path))
    assert jaxprobe.get_compile_watcher() is None
    trace.clear_spans()

    def quiet_then_watched(x):
        return x * 5 + 2

    f = jax.jit(quiet_then_watched)
    f(jnp.ones((3,), jnp.float32)).block_until_ready()
    jaxprobe.install_compile_watcher(MetricsRegistry())
    f(jnp.ones((4,), jnp.float32)).block_until_ready()
    t.flush()
    ring = [s for s in trace.recent_spans() if s.name == "jax/compile"
            and "quiet_then_watched" in str(s.attrs.get("fun_name"))]
    assert len(ring) == 2
    written = [e for e in read_events(str(tmp_path / "events.jsonl"))
               if e["name"] == "jax/compile"
               and "quiet_then_watched" in str(e.get("fun_name"))]
    assert len(written) == 1 and written[0]["kind"] == "span"
    assert written[0]["phase"] == "warmup" and written[0]["dur_s"] > 0


def test_transfer_meter_and_memory_stats():
    import numpy as np
    reg = MetricsRegistry()
    m = jaxprobe.TransferMeter(reg)
    n = m.h2d({"a": np.ones((4, 3), np.float32), "b": np.ones(2, np.float64)})
    assert n == 4 * 3 * 4 + 2 * 8
    assert reg.counter("xfer/h2d_bytes").value == n
    assert isinstance(jaxprobe.device_memory_stats(), dict)  # {} on CPU


# ---- report: summarize / render / check ------------------------------------

def _ev(name, kind="event", **attrs):
    return {"ts": 100.0, "kind": kind, "name": name, "proc": 0,
            "host": "h", **attrs}


def _sample_events():
    evs = [_ev("train/run_start")]
    evs += [_ev("jax/compile", phase="warmup", dur_s=1.0, after_warmup=False)]
    for i in range(10):
        evs.append(_ev("train/step", epoch=0, step=i,
                       dur_s=0.010 + 0.001 * i, stall_s=0.002))
    evs.append(_ev("train/epoch_end", epoch=0, dur_s=0.5, stall_s=0.02,
                   loss_train=1.25))
    evs.append(_ev("train/epoch", kind="span", epoch=0, dur_s=0.49))
    evs.append(_ev("data/reorder", kind="span", graphs=8, dur_s=0.25))
    evs.append(_ev("ckpt/save", path="e0.ckpt", epoch=0, bytes=1000,
                   dur_s=0.01))
    evs.append(_ev("serve/batch", n=64, e=256, filled=3, capacity=4,
                   dur_s=0.004))
    return evs


def test_summarize_and_render():
    s = report.summarize(_sample_events())
    assert s["n_events"] == len(_sample_events())
    assert s["steps"]["count"] == 10
    assert s["steps"]["p50_ms"] == pytest.approx(14.0, abs=1.1)
    assert s["steps"]["p99_ms"] == pytest.approx(19.0, abs=0.1)
    assert s["stall"]["stall_s"] == pytest.approx(0.02)
    frac = 0.02 / (sum(0.010 + 0.001 * i for i in range(10)) + 0.02)
    assert s["stall"]["fraction"] == pytest.approx(frac, rel=1e-3)
    assert s["compiles"]["total"] == 1
    assert s["compiles"]["after_warmup"] == 0
    assert s["checkpoints"] == {"saves": 1, "save_bytes": 1000,
                                "save_s": 0.01, "restores": 0}
    assert s["serve"]["batches"] == 1
    assert s["faults"] == []
    assert s["epochs"]["count"] == 1 and s["epochs"]["last_loss_train"] == 1.25
    assert s["spans"] == {"data/reorder": {"count": 1, "total_s": 0.25},
                          "train/epoch": {"count": 1, "total_s": 0.49}}

    text = report.render_text(s, source="x.jsonl")
    assert "steps: 10" in text and "AFTER WARMUP" in text
    assert "data/reorder" in text.split("spans:")[1]
    assert "fault timeline: clean" in text
    assert report.check(s) == []


def test_summarize_stall_falls_back_to_epochs():
    """scan-epoch runs emit no per-step events — stall comes from the
    per-epoch aggregates."""
    evs = [_ev("train/epoch_end", epoch=0, dur_s=2.0, stall_s=0.5)]
    s = report.summarize(evs)
    assert s["stall"]["stall_s"] == 0.5
    assert s["stall"]["fraction"] == pytest.approx(0.25)


def test_check_gates():
    assert report.check(report.summarize([])) != []          # zero events
    bad = report.summarize([_ev("jax/compile", phase="epoch3", dur_s=2.0,
                                after_warmup=True)])
    fails = report.check(bad)
    assert any("recompile" in f for f in fails)
    # fault timeline ordering + rendering
    evs = [_ev("train/divergence", epoch=2, msg=None),
           _ev("train/rollback", epoch=2, lr_scale=0.5)]
    evs[0]["ts"], evs[1]["ts"] = 10.0, 11.0
    s = report.summarize(evs)
    assert [f["name"] for f in s["faults"]] == ["train/divergence",
                                                "train/rollback"]
    assert "fault timeline:" in report.render_text(s)


def test_load_events_tolerates_torn_line(tmp_path):
    p = tmp_path / "e.jsonl"
    p.write_text('{"ts": 1, "kind": "event", "name": "a"}\n{"ts": 2, "ki')
    events, bad = report.load_events(str(p))
    assert len(events) == 1 and bad == 1


def test_obs_report_cli(tmp_path, clean_obs):
    t = trace.configure(log_dir=str(tmp_path))
    for i in range(3):
        t.event("train/step", epoch=0, step=i, dur_s=0.01, stall_s=0.0)
    t.flush()
    path = str(tmp_path / "events.jsonl")
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "obs_report.py"),
         path, "--check"], capture_output=True, text=True, env=env, cwd=REPO)
    assert r.returncode == 0, r.stderr
    assert "steps: 3" in r.stdout
    assert "obs_report --check: OK" in r.stderr
    # --json emits one parseable object
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "obs_report.py"),
         path, "--json"], capture_output=True, text=True, env=env, cwd=REPO)
    assert json.loads(r.stdout)["steps"]["count"] == 3
    # an empty stream fails --check
    empty = tmp_path / "empty.jsonl"
    empty.write_text("")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "obs_report.py"),
         str(empty), "--check"], capture_output=True, text=True, env=env,
        cwd=REPO)
    assert r.returncode == 1
    assert "zero events" in r.stderr


# ---- lint: no bare print( in distegnn_tpu/ ---------------------------------

def test_no_bare_prints():
    """Tier-1 wiring of scripts/check_no_print.py: runtime output goes
    through obs.log() so it reaches the event stream; escape hatches are
    '# noqa: obs-print' or the script's allowlist."""
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    try:
        from check_no_print import find_violations
    finally:
        sys.path.pop(0)
    violations = find_violations()
    assert violations == [], (
        "bare print( in distegnn_tpu/ — use obs.log() or mark the line "
        f"'# noqa: obs-print': {violations}")
