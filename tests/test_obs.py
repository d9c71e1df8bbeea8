"""Telemetry subsystem unit tier (src/repro/obs/).

JSONL schema round-trip, ring-buffer eviction, histogram percentiles,
the disabled-mode overhead guard, straggler localization from enriched
peer heartbeats, the schedule-phase named scopes surviving into compiled
HLO, and recorder spans landing on the profiler's clock.
"""
import json
import os
import time

import pytest

from repro.obs import recorder as rec_mod
from repro.obs.recorder import NULL, Recorder
from repro.obs.schema import SchemaError, validate_lines, validate_record


@pytest.fixture(autouse=True)
def _isolate_global_recorder():
    """Tests that install a global recorder must not leak it."""
    prev = rec_mod.get_recorder()
    yield
    rec_mod.set_recorder(prev)


# --------------------------------------------------------------------------
# schema round-trip
# --------------------------------------------------------------------------
def test_schema_roundtrip(tmp_path):
    d = str(tmp_path / "tel")
    with Recorder(d, flush_every=1) as r:
        r.counter("c.things", 2, host=0)
        r.gauge("g.depth", 3.5)
        r.observe("h.step_s", 0.01, step=1)
        r.event("e.fault", msg="[test] something happened", kind="host_loss")
        with r.span("s.phase", layer=0):
            pass
    path = os.path.join(d, "telemetry.jsonl")
    lines = open(path).read().splitlines()
    assert len(lines) == 5
    recs = validate_lines(lines)       # raises SchemaError on any bad line
    assert len(recs) == 5
    kinds = [json.loads(ln)["kind"] for ln in lines]
    assert kinds == ["counter", "gauge", "histogram", "event", "span"]


def test_schema_rejects_malformed():
    ok = {"ts": 1.0, "kind": "gauge", "name": "x", "value": 1}
    validate_record(ok)
    for bad in (
        {"kind": "gauge", "name": "x", "value": 1},            # no ts
        {"ts": 1.0, "kind": "nope", "name": "x"},              # bad kind
        {"ts": 1.0, "kind": "gauge", "name": "x"},             # no value
        {"ts": 1.0, "kind": "gauge", "name": "x", "value": "y"},
        {"ts": 1.0, "kind": "span", "name": "x", "dur_s": -1},
        {"ts": 1.0, "kind": "event", "name": "x", "bogus": 1},  # extra field
        {"ts": 1.0, "kind": "event", "name": "x",
         "tags": {"nested": {"a": 1}}},                        # non-flat tag
    ):
        with pytest.raises(SchemaError):
            validate_record(bad)


# --------------------------------------------------------------------------
# ring buffer / aggregates
# --------------------------------------------------------------------------
def test_ring_eviction():
    r = Recorder(ring_size=4)
    for i in range(10):
        r.gauge("g", i)
    assert len(r.ring) == 4
    assert [rec["value"] for rec in r.ring] == [6, 7, 8, 9]


def test_histogram_percentiles():
    r = Recorder()
    for v in range(1, 101):
        r.observe("h", v)
    assert r.percentile("h", 0) == 1
    assert r.percentile("h", 100) == 100
    assert r.percentile("h", 50) in (50, 51)      # nearest-rank
    assert r.percentile("h", 90) in (90, 91)
    assert r.percentile("h", 99) in (99, 100)
    assert r.percentile("missing", 50) is None
    s = r.summary()["histograms"]["h"]
    assert s["count"] == 100 and abs(s["mean"] - 50.5) < 1e-9


def test_counters_gauges_aggregate():
    r = Recorder()
    r.counter("c", 1)
    r.counter("c", 2)
    r.gauge("g", 7)
    r.gauge("g", 9)
    s = r.summary()
    assert s["counters"]["c"] == 3
    assert s["gauges"]["g"] == 9


def test_span_records_duration():
    r = Recorder()
    with r.span("phase", layer=3):
        time.sleep(0.001)
    rec = r.ring[-1]
    assert rec["kind"] == "span" and rec["name"] == "phase"
    assert rec["dur_s"] >= 0.001
    assert rec["tags"] == {"layer": 3}


def test_console_passthrough_keeps_legacy_lines():
    seen = []
    r = Recorder(console=seen.append)
    r.event("trainer.step", msg="[trainer] step 10 loss 2.0")
    r.gauge("g", 1)                    # non-events never hit the console
    assert seen == ["[trainer] step 10 loss 2.0"]


def test_flush_every_must_be_positive(tmp_path):
    with pytest.raises(ValueError, match="positive"):
        Recorder(str(tmp_path), flush_every=0)


# --------------------------------------------------------------------------
# disabled-mode overhead guard
# --------------------------------------------------------------------------
def test_null_recorder_overhead():
    """NullRecorder calls must stay near-zero (~0.1µs measured); the 2µs
    bound is generous for CI jitter but still catches an accidental
    allocation or dict build on the disabled path."""
    n = 200_000
    NULL.counter("warm")
    t0 = time.perf_counter()
    for _ in range(n):
        NULL.counter("x", 1, step=0)
    per_call = (time.perf_counter() - t0) / n
    assert per_call < 2e-6, f"disabled-mode cost {per_call*1e9:.0f} ns/call"
    with NULL.span("x"):
        pass


# --------------------------------------------------------------------------
# straggler localization from enriched peer heartbeats
# --------------------------------------------------------------------------
def _write_hb(path, host, ewma):
    with open(path, "w") as f:
        json.dump({"step": 10, "time": time.time(), "host": host,
                   "step_time_s": ewma, "step_time_ewma_s": ewma}, f)


def test_straggler_localization(tmp_path):
    from repro.runtime.elastic import StragglerEscalation
    paths = {}
    for h, ewma in enumerate([0.10, 0.11, 0.10, 0.50]):
        p = str(tmp_path / f"hb{h}.json")
        _write_hb(p, h, ewma)
        paths[h] = p
    esc = StragglerEscalation(peer_paths=paths)
    host, detail = esc.localize()
    assert host == 3
    assert "h3=500.0ms" in detail

    # escalation carries the localized host into the FaultEvent
    esc = StragglerEscalation(peer_paths=paths, escalate_after=1)
    for step in range(8):
        assert esc.observe_step(step, 0.1) is None
    ev = esc.observe_step(8, 1.0)
    assert ev is not None and ev.kind == "straggler"
    assert ev.host == 3
    assert "per-host ewma" in ev.detail


def test_straggler_localization_no_outlier(tmp_path):
    from repro.runtime.elastic import StragglerEscalation
    paths = {}
    for h in range(3):
        p = str(tmp_path / f"hb{h}.json")
        _write_hb(p, h, 0.1)
        paths[h] = p
    assert StragglerEscalation(peer_paths=paths).localize()[0] is None
    # <2 readable peers -> no localization
    assert StragglerEscalation(
        peer_paths={0: paths[0]}).localize() == (None, "")


def test_read_heartbeat_tolerates_garbage(tmp_path):
    from repro.runtime.elastic import read_heartbeat
    assert read_heartbeat(str(tmp_path / "missing.json")) is None
    p = str(tmp_path / "bad.json")
    open(p, "w").write("{half a rec")
    assert read_heartbeat(p) is None


# --------------------------------------------------------------------------
# report CLI
# --------------------------------------------------------------------------
def test_report_render_and_validate(tmp_path, capsys):
    d = str(tmp_path / "tel")
    with Recorder(d, flush_every=1) as r:
        for i in range(5):
            r.observe("trainer.step_time_s", 0.01 * (i + 1), step=i)
        r.counter("serving.decoded_tokens", 64)
        r.gauge("serving.queue_depth", 2)
        r.event("trainer.restore", msg="[trainer] restored step 5")
    from repro.obs import report
    assert report.main([d, "--validate"]) == 0
    out = capsys.readouterr().out
    assert "telemetry records OK" in out
    assert report.main([d]) == 0
    out = capsys.readouterr().out
    assert "per-phase breakdown" in out
    assert "trainer.step_time_s" in out
    assert "== events" in out and "restored step 5" in out


def test_report_validate_catches_corruption(tmp_path, capsys):
    d = str(tmp_path / "tel")
    with Recorder(d, flush_every=1) as r:
        r.gauge("g", 1)
    with open(os.path.join(d, "telemetry.jsonl"), "a") as f:
        f.write('{"ts": 1.0, "kind": "nope", "name": "x"}\n')
    from repro.obs import report
    assert report.main([d, "--validate"]) == 1


# --------------------------------------------------------------------------
# schedule-phase tracing survives into compiled HLO
# --------------------------------------------------------------------------
def test_phase_scope_visible_in_compiled_hlo():
    import jax
    import jax.numpy as jnp

    from repro.obs.tracing import phase_scope

    def f(x):
        with phase_scope("obs_probe_scope"):
            return (x * 2.0).sum()

    txt = jax.jit(f).lower(jnp.ones((4, 4))).compile().as_text()
    assert "obs_probe_scope" in txt


def test_trace_annotation_is_reentrant():
    from repro.obs.tracing import trace_annotation
    with trace_annotation("outer"):
        with trace_annotation("inner"):
            pass


def _host_events(trace_dir):
    import glob

    from jax.profiler import ProfileData
    f = max(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True), key=os.path.getmtime)
    return [(e.name, e.start_ns, e.duration_ns)
            for p in ProfileData.from_file(f).planes
            if p.name.startswith("/host:") for ln in p.lines
            for e in ln.events]


def test_recorder_span_on_profiler_clock(tmp_path):
    """A recorder span is also a profiler annotation of its name, as long
    as the span it records."""
    import jax
    r = Recorder()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with r.span("train.obs_test_span", layer=1):
            time.sleep(0.01)
    finally:
        jax.profiler.stop_trace()
    ev = [e for e in _host_events(str(tmp_path))
          if e[0] == "train.obs_test_span"]
    assert len(ev) == 1
    rec = [x for x in r.ring if x["kind"] == "span"][0]
    assert rec["dur_s"] == pytest.approx(ev[0][2] * 1e-9, abs=2e-3)


# --------------------------------------------------------------------------
# global recorder plumbing
# --------------------------------------------------------------------------
def test_configure_installs_global(tmp_path):
    d = str(tmp_path / "tel")
    r = rec_mod.configure(d, flush_every=1)
    try:
        assert rec_mod.get_recorder() is r
        rec_mod.get_recorder().gauge("g", 1)
        r.flush()
        assert len(validate_lines(
            open(os.path.join(d,
                              "telemetry.jsonl")).read().splitlines())) == 1
    finally:
        r.close()


def test_set_recorder_none_restores_null():
    rec_mod.set_recorder(Recorder())
    rec_mod.set_recorder(None)
    assert rec_mod.get_recorder() is NULL
