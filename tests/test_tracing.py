"""The program's span and counter recorder (repro.tracing), the page pool's
host-device copy counters, and the serving engine's spans."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tracing
from repro.memory.pool import PagePool
from repro.serving.engine import ServingEngine


@pytest.fixture()
def tracer(monkeypatch):
    """A fresh recorder in the module's place, so the program's spans and
    the compile listeners land in it alone."""
    tr = tracing.Tracer()
    monkeypatch.setattr(tracing, "TRACER", tr)
    return tr


def _copies(span):
    """A span's page-pool copy counts (compiles left out)."""
    return {k: v for k, v in (span.counts or {}).items()
            if k.startswith("pool.")}


def _by_name(records):
    out = {}
    for r in records:
        out.setdefault(r.name, []).append(r)
    return out


def test_nesting_parent_and_request_ids(tracer):
    with tracing.span("outer", 7) as outer:
        with tracing.span("inner") as inner:
            with tracing.span("leaf", (1, 2)) as leaf:
                pass
    assert [r.name for r in tracer.ring] == ["leaf", "inner", "outer"]
    assert outer.parent is None
    assert inner.parent == outer.id and leaf.parent == inner.id
    # a child without ids serves its parent's request
    assert (outer.rid, inner.rid, leaf.rid) == (7, 7, (1, 2))
    assert outer.t0 <= inner.t0 <= leaf.t0 <= leaf.t1 <= inner.t1 <= outer.t1
    assert not tracer._stack


def test_self_counts_go_to_the_innermost_span(tracer):
    tracing.count("pool.h2d_bytes", 5)           # no span open: totals only
    with tracing.span("outer") as outer:
        tracing.count("pool.h2d_bytes", 10)
        with tracing.span("inner") as inner:
            tracing.count("pool.h2d_bytes", 100)
            tracing.count("pool.h2d_copies")
        tracing.count("pool.h2d_copies")
    assert outer.counts == {"pool.h2d_bytes": 10, "pool.h2d_copies": 1}
    assert inner.counts == {"pool.h2d_bytes": 100, "pool.h2d_copies": 1}
    assert tracer.totals == {"pool.h2d_bytes": 115, "pool.h2d_copies": 2}


def test_wait_goes_to_the_ring_with_its_own_start(tracer):
    t0 = time.perf_counter()
    with tracing.span("step") as step:
        tracing.wait("engine.queue", t0, rid=3)
    q = _by_name(tracer.ring)["engine.queue"][0]
    assert (q.t0, q.rid, q.parent) == (t0, 3, None)
    assert q.t0 <= step.t0 <= q.t1 <= step.t1
    assert q.id != step.id and not tracer._stack


def test_window_filters_by_interval(tracer):
    with tracing.span("before"):
        pass
    t0 = time.perf_counter()
    with tracing.span("a"):
        with tracing.span("b"):
            pass
    t1 = time.perf_counter()
    with tracing.span("after"):
        pass
    assert [r.name for r in tracer.window(t0, t1)] == ["b", "a"]
    assert tracer.window(t1, t1) == []


def test_ring_overflow_is_reported_not_undercounted():
    tr = tracing.Tracer(capacity=4)
    for _ in range(3):
        with tr.span("old"):
            pass
    t0 = time.perf_counter()
    for _ in range(3):
        with tr.span("new"):
            pass
    with tr.span("new"):
        pass
    # three "old" records were dropped, all before t0
    assert [r.name for r in tr.window(t0, time.perf_counter())] == ["new"] * 4
    with tr.span("newer"):
        pass
    # the ring has dropped a record from inside [t0, ...]
    assert tr.window(t0, time.perf_counter()) is None
    assert len(tr.ring) == 4


def test_a_forced_compile_is_counted_once_under_its_span(tracer):
    x = np.arange(7, dtype=np.float32)
    f = jax.jit(lambda v: v * 3.0 + 1.0)     # a new function: compiles
    with tracing.span("outer") as outer:
        with tracing.span("compile") as span:
            f(x).block_until_ready()
        f(x).block_until_ready()            # compiled already: no event
    c = span.counts or {}
    assert c.get("compile.backend", 0) + c.get("compile.cache_hits", 0) == 1
    assert (c.get("compile.backend_s", 0) > 0) == ("compile.backend" in c)
    assert outer.counts is None


def _pool_action(name):
    """One call on one path of the page pool's data plane."""
    dt = jnp.float32
    host, dev = PagePool(page_elems=128), PagePool(page_elems=128, device=True)
    hf, df = host.alloc(dt, 3), dev.alloc(dt, 3)
    pages = np.ones((3, 128), np.float32)
    rows = np.ones((3, 1, 128), np.float32)
    return {
        "host.write_pages.device": lambda: host.write_pages(dt, hf,
                                                            jnp.asarray(pages)),
        "host.write_pages.host": lambda: host.write_pages(dt, hf, pages),
        "host.write_rows.device": lambda: host.write_rows(
            dt, hf, [0, 0, 0], jnp.asarray(rows)),
        "host.read_pages": lambda: host.read_pages(dt, hf),
        "host.read_pages_host": lambda: host.read_pages_host(dt, hf),
        "host.assemble": lambda: host.assemble(dt, hf, (3, 100)),
        "host.frames_array": lambda: host.frames_array(dt),
        "device.write_pages.host": lambda: dev.write_pages(dt, df, pages),
        "device.write_pages.device": lambda: dev.write_pages(
            dt, df, jnp.asarray(pages)),
        "device.write_rows.host": lambda: dev.write_rows(dt, df, [0, 0, 0],
                                                          rows),
        "device.read_pages_host": lambda: dev.read_pages_host(dt, df),
        "device.frames_array": lambda: dev.frames_array(dt),
    }[name]


# path -> (way, bytes) of the one copy it makes across the boundary
CROSSINGS = {
    "host.write_pages.device": ("d2h", 3 * 128 * 4),
    "host.write_pages.host": None,
    "host.write_rows.device": ("d2h", 3 * 128 * 4),
    "host.read_pages": ("h2d", 3 * 128 * 4),
    "host.read_pages_host": None,
    "host.assemble": ("h2d", 300 * 4),
    "host.frames_array": ("h2d", 256 * 128 * 4),   # the grown pool, whole
    "device.write_pages.host": ("h2d", 3 * 128 * 4),
    "device.write_pages.device": None,
    "device.write_rows.host": ("h2d", 3 * 128 * 4),
    "device.read_pages_host": ("d2h", 3 * 128 * 4),
    "device.frames_array": None,
}


@pytest.mark.parametrize("path", sorted(CROSSINGS))
def test_pool_counts_each_copy_across_the_boundary(tracer, path):
    action = _pool_action(path)
    with tracing.span("copy") as span:
        action()
    want = CROSSINGS[path]
    if want is None:
        assert _copies(span) == {}
    else:
        way, nbytes = want
        assert _copies(span) == {f"pool.{way}_bytes": nbytes,
                                 f"pool.{way}_copies": 1}


@pytest.fixture(scope="module")
def served(hello_cfg, hello_params):
    """Two requests through an engine with a host KV pool, recorded."""
    tr = tracing.Tracer()
    saved, tracing.TRACER = tracing.TRACER, tr
    try:
        eng = ServingEngine(hello_cfg, hello_params, page_tokens=4,
                            backend="ref")
        prompts = {eng.submit([3, 1, 4, 1, 5], max_tokens=3): 5,
                   eng.submit(list(range(1, 10)), max_tokens=4): 9}
        eng.run_to_completion()
    finally:
        tracing.TRACER = saved
    return eng, prompts, list(tr.ring)


def test_engine_spans_one_queue_wait_and_prefill_per_request(served):
    eng, prompts, recs = served
    by = _by_name(recs)
    for name in ("engine.queue", "engine.prefill"):
        assert sorted(r.rid for r in by[name]) == sorted(prompts), name
    for q in by["engine.queue"]:
        p = next(r for r in by["engine.prefill"] if r.rid == q.rid)
        assert q.t1 <= p.t0
    steps = {r.id for r in by["engine.step"]}
    for name in ("engine.prefill", "engine.decode"):
        assert all(r.parent in steps for r in by[name])
    pre = {r.id: r for r in by["engine.prefill"]}
    for name in ("lm.prefill", "kv.write_prefill", "engine.first_token"):
        assert sorted(pre[r.parent].rid for r in by[name]) == sorted(prompts)


def test_write_prefill_copies_two_pages_a_column(served, hello_cfg):
    """A K and a V page per layer and column, all in one copy."""
    eng, prompts, recs = served
    pre = {r.id: r.rid for r in recs if r.name == "engine.prefill"}
    L, K, hd = hello_cfg.num_layers, hello_cfg.num_kv_heads, hello_cfg.head_dim
    spans = [r for r in recs if r.name == "kv.write_prefill"]
    assert len(spans) == len(prompts)
    for r in spans:
        cols = -(-prompts[pre[r.parent]] // eng.kv.Tp)
        assert _copies(r) == {"pool.d2h_copies": 1,
                              "pool.d2h_bytes": 2 * cols * L * eng.kv.Tp
                              * K * hd * 4}


def test_decode_copies_the_pool_each_layer_and_one_row_a_request(
        served, hello_cfg):
    eng, _, recs = served
    L, K, hd = hello_cfg.num_layers, hello_cfg.num_kv_heads, hello_cfg.head_dim
    frames = eng.kv.pool._frames["float32"].shape[0]
    page_bytes = eng.kv.Tp * K * hd * 4
    by = _by_name(recs)
    layer = {r.id: r for r in by["lm.decode"]}
    decode = {r.id: r for r in by["engine.decode"]}
    assert len(by["kv.frames_view"]) == len(by["kv.write_token"]) \
        == L * len(decode)
    for r in by["kv.frames_view"]:
        assert _copies(r) == {"pool.h2d_bytes": frames * page_bytes,
                              "pool.h2d_copies": 1}
    for r in by["kv.write_token"]:
        assert _copies(r) == {"pool.d2h_bytes": 2 * len(r.rid) * K * hd * 4,
                              "pool.d2h_copies": 2}
    for d in decode.values():
        under = [r for r in by["kv.frames_view"] + by["kv.write_token"]
                 if layer[r.parent].parent == d.id]
        got = sum(_copies(r).get("pool.h2d_bytes", 0)
                  + _copies(r).get("pool.d2h_bytes", 0) for r in under)
        assert got == L * frames * page_bytes + L * 2 * len(d.rid) * K * hd * 4
