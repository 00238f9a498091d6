"""The readers of the program's own spans and counters (``program.py`` and
the metrics that use it), and the program's spans in the profiler's trace
beside the harness's."""
import json
import sys
import time
import types
import warnings
from pathlib import Path

import pytest

import repro
from chipbench import run, spec
from chipbench import trace_reduce as tr
from chipbench.spans import Spans
from chipbench.tests import tiny
from repro import tracing

DATA = Path(__file__).parent / "data"
NEW = ("queue_wait_ms", "kv_write_prefill_ms", "host_copy_mib.decode",
       "compiles_in_window")


def _read(name, rec):
    return spec.metric_reader(name).read(rec)


def _record(trc, name, t0, t1, parent=None, counts=None, rid=None):
    r = tracing.Span(trc, name, rid)
    r.id, r.t0, r.t1, r.parent, r.counts = next(trc._ids), t0, t1, parent, \
        counts
    trc._finish(r)
    return r


def _window_rec(t0=100.0, t1=200.0):
    sp = Spans()
    sp.spans["window"].append((t0, t1))
    return types.SimpleNamespace(spans=sp)


@pytest.fixture()
def trc(monkeypatch):
    """The program's recorder, holding a window's worth of records: two
    requests admitted, two prefills, two decode steps; and records before
    the window that no reader may count."""
    t = tracing.Tracer()
    monkeypatch.setattr(tracing, "TRACER", t)
    _record(t, "engine.queue", 50.0, 60.0, rid=0)
    _record(t, "kv.write_prefill", 61.0, 62.0, counts={"compile.backend": 3})
    _record(t, "engine.queue", 101.0, 101.1, rid=1)
    _record(t, "engine.queue", 102.0, 102.3, rid=2)
    for t0, secs, rid in ((110.0, 0.5, 1), (120.0, 0.7, 2)):
        p = _record(t, "engine.prefill", t0, t0 + 2, rid=rid)
        _record(t, "lm.prefill", t0, t0 + 1, parent=p.id,
                counts={"compile.cache_hits": 1})
        _record(t, "kv.write_prefill", t0 + 1, t0 + 1 + secs, parent=p.id,
                counts={"pool.d2h_bytes": 4096, "pool.d2h_copies": 2})
    for t0, batch in ((130.0, 1), (131.0, 2)):
        d = _record(t, "engine.decode", t0, t0 + 0.9, rid=tuple(range(batch)))
        layer = _record(t, "lm.decode", t0 + 0.1, t0 + 0.8, parent=d.id)
        for i in range(2):
            _record(t, "kv.write_token", t0 + 0.1 + i * 0.3,
                    t0 + 0.2 + i * 0.3, parent=layer.id,
                    counts={"pool.d2h_bytes": 512 * batch,
                            "pool.d2h_copies": 2})
            _record(t, "kv.frames_view", t0 + 0.2 + i * 0.3,
                    t0 + 0.3 + i * 0.3, parent=layer.id,
                    counts={"pool.h2d_bytes": 2**20, "pool.h2d_copies": 1,
                            "compile.backend": 1, "compile.backend_s": 0.5})
    return t


def test_readers_of_the_program_records(trc):
    rec = _window_rec()
    assert _read("queue_wait_ms", rec) == pytest.approx(200.0)
    assert _read("kv_write_prefill_ms", rec) == pytest.approx(600.0)
    # each step: 2 layers x (1 MiB pool to the device + 512 B a request back)
    assert _read("host_copy_mib.decode", rec) == pytest.approx(
        (2 * 2**20 + 2 * 512 * 1.5) / 2**20)
    assert _read("compiles_in_window", rec) == 2 + 4


@pytest.mark.parametrize("name", NEW)
def test_no_window_no_records_or_an_overflowed_ring_is_none(trc, name):
    assert _read(name, types.SimpleNamespace(spans=Spans())) is None
    assert _read(name, _window_rec(300.0, 400.0)) is None
    trc.dropped_until = 100.5
    assert _read(name, _window_rec()) is None


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_recorder_is_none(trc, monkeypatch, name):
    monkeypatch.delattr(repro, "tracing")
    monkeypatch.setitem(sys.modules, "repro.tracing", None)
    assert _read(name, _window_rec()) is None


def test_recorded_v5e_trace_reduces_as_before():
    """Every key of the recorded trace's reduction, ``idle_gaps`` included,
    as ``v5e_small.reduce.json`` holds it."""
    red = json.loads(json.dumps(tr.reduce(DATA / "v5e_small.xplane.pb")))
    assert red == json.loads((DATA / "v5e_small.reduce.json").read_text())


def _profile(tmp_path):
    """A profile of the harness's window holding the program's spans as the
    engine nests them."""
    import jax
    jax.profiler.start_trace(str(tmp_path))
    try:
        with Spans(annotate=True).span("window"):
            with tracing.span("engine.step"):
                with tracing.span("engine.prefill", 7):
                    pass
    finally:
        jax.profiler.stop_trace()
    return next(tmp_path.rglob("*.xplane.pb"))


def test_program_spans_leave_the_reductions_spans_as_they_were(tmp_path):
    """The reduction reads the harness's spans alone: the program's
    ``repro.`` annotations change none of its keys."""
    _, host = tr.read_planes(_profile(tmp_path))
    assert [n for n, _, _ in host] == ["window"]


def test_program_spans_land_on_the_profilers_host_plane(tmp_path):
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(_profile(tmp_path)))
    host = [(e.name, e.start_ns, e.end_ns) for plane in data.planes
            if plane.name.startswith("/host:") for line in plane.lines
            for e in line.events]
    (w0, w1), = [(s, e) for n, s, e in host if n == "cb.window"]
    (s0, s1), = [(s, e) for n, s, e in host if n == "repro.engine.step"]
    (p0, p1), = [(s, e) for n, s, e in host if n == "repro.engine.prefill"]
    assert w0 <= s0 <= p0 <= p1 <= s1 <= w1


def test_traced_tiny_serving_run_reports_the_program_metrics():
    """The serving cell's traced run on the tiny configuration, on the CPU:
    no device plane, so only the host's spans and the program's records
    are read."""
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        r = run.run_cell(spec.load_benchmark(), tiny.workload("serve"),
                         2**31 + 4099, 1.0, True, conf=tiny.TINY_CONF,
                         mix=tiny.SERVE_MIX, limits=tiny.LIMITS,
                         device_kind="TPU v5 lite",
                         t_start=time.perf_counter())
    assert r["correct"] and r["failed"] == 0
    m = {k: v["value"] for k, v in r["metrics"].items()}
    assert set(NEW) <= set(m)
    assert m["queue_wait_ms"] >= 0 and m["kv_write_prefill_ms"] > 0
    assert m["compiles_in_window"] >= 0
    # the whole KV pool to the device on each layer of each step, and one
    # K and one V row back per request and layer (bf16)
    conf, mix = tiny.TINY_CONF, tiny.SERVE_MIX
    L, K, hd = conf["num_hidden_layers"], conf["num_key_value_heads"], \
        conf["run"]["head_dim"]
    tp, n = mix["kv_page_tokens"], mix["max_active"]
    longest = max(mix["prompt_tokens"]["values"]) + mix["output_tokens"]
    frames = n * 2 * L * -(-(longest + 1) // tp)
    pool = L * frames * tp * K * hd * 2
    rows = [L * 2 * b * K * hd * 2 for b in (1, n)]
    assert (pool + rows[0]) / 2**20 <= m["host_copy_mib.decode"] \
        <= (pool + rows[1]) / 2**20
