"""Telemetry-layer tests: metrics registry determinism, the Prometheus
golden, the cross-host span-merge byte-identity property, the timed-span
ring, the logging facade render format, and the instrumentation-overhead
guard.

The determinism tests pin the exact-reproduction contract the benches
rely on: two runs that record the same observations serialize to
byte-identical JSONL, and histograms carry exact count/sum/min/max so
statistics previously computed harness-side (MTTR mean/max, goodput)
reproduce bit-for-bit from a snapshot.
"""
import logging
import random
import time

import pytest

from _hypothesis_compat import given, settings, st
from repro.obs import metrics, report, trace
from repro.obs.logging import get_logger
from repro.obs.logging import configure as obs_configure


# ------------------------------------------------------- bucket edges
def test_log_buckets_golden():
    """Edges are pure ``**`` rounded to 9 significant digits: fixed
    constants, not wall-clock- or platform-dependent."""
    edges = metrics.log_buckets(1e-4, 1e3, 15)
    assert edges == metrics.DEFAULT_BUCKETS
    assert len(edges) == 15
    assert edges[0] == 1e-4 and edges[-1] == 1e3
    assert all(a < b for a, b in zip(edges, edges[1:]))
    # recomputation is bit-identical (no accumulated-multiply drift)
    assert edges == metrics.log_buckets(1e-4, 1e3, 15)
    # one interior golden value pins the 9-sig-digit rounding rule
    assert edges[7] == float(f"{1e-4 * 1e7 ** (7 / 14):.9g}")


def test_log_buckets_rejects_bad_ranges():
    for lo, hi, n in [(0.0, 1.0, 4), (1.0, 1.0, 4), (2.0, 1.0, 4),
                      (0.1, 1.0, 1)]:
        with pytest.raises(ValueError):
            metrics.log_buckets(lo, hi, n)


# -------------------------------------------------- snapshot determinism
def _record(reg: metrics.Registry):
    with metrics.use(reg):
        with metrics.label_scope(section="unit"):
            for v in [0.0625, 0.5, 0.5, 5.0, 0.0009765625]:
                metrics.observe("mttr_seconds", v)
            metrics.inc("serve_completed_total", 7)
            metrics.inc("kv_retries_total", 3, op="get")
            metrics.set_gauge("serve_virtual_time_seconds", 12.25)


def test_two_registries_byte_identical():
    a, b = metrics.Registry(), metrics.Registry()
    _record(a)
    _record(b)
    assert a.to_jsonl() == b.to_jsonl()
    assert a.to_prometheus() == b.to_prometheus()
    assert a.snapshot() == b.snapshot()


def test_histogram_exact_stats():
    """count/sum/min/max are exact (sum in observation order), and the
    bucket counts partition the observations."""
    reg = metrics.Registry()
    vals = [0.0625, 0.5, 0.5, 5.0, 0.0009765625]
    with metrics.use(reg), metrics.label_scope(section="unit"):
        for v in vals:
            metrics.observe("mttr_seconds", v)
    snap = reg.snapshot()
    st_ = report.hist_stats(snap, "mttr_seconds", section="unit")
    acc = 0.0
    for v in vals:
        acc += v
    assert st_["count"] == len(vals)
    assert st_["sum"] == acc            # bit-exact, not approx
    assert st_["min"] == min(vals) and st_["max"] == max(vals)
    fam = report.family(snap, "mttr_seconds")
    assert sum(fam["samples"][0]["bucket_counts"]) == len(vals)


def test_label_scope_only_applies_declared_labels():
    """A scope's ``section`` reaches only families that declare it;
    explicit labels win over the scope."""
    reg = metrics.Registry()
    with metrics.use(reg), metrics.label_scope(section="outer", op="x"):
        metrics.inc("kv_retries_total")              # op <- scope
        metrics.inc("kv_retries_total", op="put")    # explicit wins
        metrics.observe("train_step_seconds", 0.5)   # declares no labels
    snap = reg.snapshot()
    assert report.counter_value(snap, "kv_retries_total", op="x") == 1
    assert report.counter_value(snap, "kv_retries_total", op="put") == 1
    assert report.hist_stats(snap, "train_step_seconds")["count"] == 1


def test_hist_stats_refuses_to_merge_children():
    """Exact float sums never merge across label children — a query
    matching several must raise, not silently add."""
    reg = metrics.Registry()
    with metrics.use(reg):
        metrics.observe("mttr_seconds", 1.0, section="a")
        metrics.observe("mttr_seconds", 2.0, section="b")
    with pytest.raises(ValueError):
        report.hist_stats(reg.snapshot(), "mttr_seconds")


def test_unknown_family_raises():
    reg = metrics.Registry()
    with pytest.raises(KeyError):
        reg.inc("not_in_schema_total")
    with pytest.raises(TypeError):
        reg.observe("kv_retries_total", 1.0)  # declared counter


# ---------------------------------------------------- Prometheus golden
def test_prometheus_golden():
    """Byte-for-byte exposition golden over all three kinds (dyadic
    values, so every float renders exactly)."""
    reg = metrics.Registry()
    reg.declare("rpc_seconds", metrics.HISTOGRAM, "rpc time", ("op",),
                (0.125, 1.0))
    reg.declare("reqs_total", metrics.COUNTER, "requests", ("code",))
    reg.declare("up", metrics.GAUGE, "liveness")
    reg.inc("reqs_total", code="200")
    reg.inc("reqs_total", 2, code="500")
    reg.set_gauge("up", 1)
    for v in (0.0625, 0.5, 5.0):
        reg.observe("rpc_seconds", v, op="get")
    golden = "\n".join([
        "# HELP reqs_total requests",
        "# TYPE reqs_total counter",
        'reqs_total{code="200"} 1',
        'reqs_total{code="500"} 2',
        "# HELP rpc_seconds rpc time",
        "# TYPE rpc_seconds histogram",
        'rpc_seconds_bucket{op="get",le="0.125"} 1',
        'rpc_seconds_bucket{op="get",le="1"} 2',
        'rpc_seconds_bucket{op="get",le="+Inf"} 3',
        'rpc_seconds_sum{op="get"} 5.5625',
        'rpc_seconds_count{op="get"} 3',
        'rpc_seconds_min{op="get"} 0.0625',
        'rpc_seconds_max{op="get"} 5',
        "# HELP up liveness",
        "# TYPE up gauge",
        "up 1",
    ]) + "\n"
    assert reg.to_prometheus() == golden


# ------------------------------------------------- span-merge property
def _two_host_trace():
    """A fixed 2-host trace with overlapping request spans, fault
    annotations and an identical-name span on both hosts."""
    t0, t1 = trace.Tracer(origin=0), trace.Tracer(origin=1)
    t0.span_start(0, "req:0", rid=0)
    t0.annotate(1, "fault", stage="flash_attention", fault="transient")
    t1.span_start(1, "req:1", rid=1)
    t0.span_end(3, "req:0", tokens=17)
    t1.annotate(3, "probation", verdict="transient_recovered")
    t1.span_end(6, "req:1", tokens=9)
    t0.span_start(4, "ckpt")
    t0.span_end(5, "ckpt")
    return t0.events, t1.events


@settings(max_examples=40)
@given(seed=st.integers(min_value=0, max_value=10 ** 9),
       split=st.integers(min_value=0, max_value=12),
       dups=st.lists(st.integers(min_value=0, max_value=11),
                     min_size=0, max_size=6))
def test_span_merge_byte_identical_any_interleaving(seed, split, dups):
    """ISSUE acceptance: the merged 2-host trace serializes to the same
    bytes regardless of delivery order, partitioning, or duplication —
    the sorted-dedup union over the (step, origin, seq) logical clock
    is one value."""
    a, b = _two_host_trace()
    golden = trace.to_jsonl(trace.merge(a, b))
    delivered = list(a) + list(b)
    delivered += [delivered[i % len(delivered)] for i in dups]
    random.Random(seed).shuffle(delivered)
    cut = min(split, len(delivered))
    merged = trace.merge(delivered[:cut], delivered[cut:])
    assert trace.to_jsonl(merged) == golden
    # and a wire round-trip of the merged trace is the identity
    assert trace.to_jsonl(trace.from_jsonl(golden)) == golden


def test_spans_pair_by_name_in_clock_order():
    a, b = _two_host_trace()
    spans = trace.spans_of(trace.merge(a, b))
    by_name = {s.name: s for s in spans}
    assert by_name["req:0"].steps == 3
    assert by_name["req:1"].steps == 5
    assert by_name["ckpt"].steps == 1
    assert all(s.end is not None for s in spans)


def test_tracer_seq_monotone_and_kinds_checked():
    t = trace.Tracer(origin=2)
    evs = [t.annotate(5, "x"), t.annotate(5, "y"), t.annotate(4, "z")]
    assert [e.seq for e in evs] == [0, 1, 2]
    assert sorted(evs) == [evs[2], evs[0], evs[1]]  # clock order
    with pytest.raises(ValueError):
        trace.TraceEvent(step=0, origin=0, seq=0, kind="bogus")


# -------------------------------------------------------- timed spans
def _dur(s):
    return s.end - s.start


def test_timed_spans_nest_and_link_parents():
    t0 = time.perf_counter()
    with trace.span("obs.outer") as outer:
        with trace.span("obs.a"):
            with trace.span("obs.a.leaf"):
                pass
        with trace.span("obs.b"):
            pass
    spans, whole = trace.recent_spans(t0)
    assert whole
    by = {s.name: s for s in spans}
    assert [s.name for s in spans] == ["obs.a.leaf", "obs.a", "obs.b",
                                       "obs.outer"]      # in closing order
    assert by["obs.outer"].id == outer.id
    assert by["obs.outer"].parent == -1
    assert by["obs.a"].parent == by["obs.b"].parent == outer.id
    assert by["obs.a.leaf"].parent == by["obs.a"].id
    assert by["obs.outer"].start <= by["obs.a"].start <= by["obs.a"].end \
        <= by["obs.b"].start <= by["obs.b"].end <= by["obs.outer"].end
    assert _dur(by["obs.a"]) + _dur(by["obs.b"]) <= _dur(by["obs.outer"])
    # the window selects by overlap
    late, _ = trace.recent_spans(by["obs.b"].start)
    assert {s.name for s in late} == {"obs.b", "obs.outer"}
    assert trace.recent_spans(t0, t0)[0] == ()


def test_timed_span_attrs_carried_and_set_inside():
    t0 = time.perf_counter()
    with trace.span("obs.req", rid=12, prompt_len=64) as sp:
        sp.set(admitted=2)
    (s,), _ = trace.recent_spans(t0)
    assert s.attrs == {"rid": 12, "prompt_len": 64, "admitted": 2}


def test_timed_span_closes_on_exception():
    t0 = time.perf_counter()
    with pytest.raises(RuntimeError):
        with trace.span("obs.outer"):
            with trace.span("obs.raises"):
                raise RuntimeError("boom")
    spans, _ = trace.recent_spans(t0)
    assert [s.name for s in spans] == ["obs.raises", "obs.outer"]
    with trace.span("obs.after"):     # the open-span stack unwound
        pass
    assert trace.recent_spans(t0)[0][-1].parent == -1


def test_span_ring_is_bounded_and_reports_its_reach():
    t0 = time.perf_counter()
    with trace.span("obs.first"):
        pass
    assert trace.recent_spans(t0)[1]
    for _ in range(trace.SPAN_RING):
        with trace.span("obs.fill"):
            pass
    spans, whole = trace.recent_spans(t0)
    assert len(spans) == trace.SPAN_RING and not whole
    assert "obs.first" not in {s.name for s in spans}
    # from the oldest span still held, the ring is whole again
    assert trace.recent_spans(spans[0].start)[1]
    t1 = time.perf_counter()
    with trace.span("obs.last"):
        pass
    last, whole = trace.recent_spans(t1)
    assert whole and [s.name for s in last] == ["obs.last"]


def test_timed_spans_reach_an_active_profiler_trace(tmp_path):
    """Without a profiler trace the ring alone records; under one, each
    span is also a host event of the same name carrying its attributes."""
    import glob

    import jax
    from jax.profiler import ProfileData

    t0 = time.perf_counter()
    with trace.span("obs.untraced", rid=1):
        pass
    jax.profiler.start_trace(str(tmp_path))
    try:
        with trace.span("obs.traced", rid=3) as sp:
            with trace.span("obs.traced.child"):
                pass
            sp.set(active=2)
    finally:
        jax.profiler.stop_trace()
    assert [s.name for s in trace.recent_spans(t0)[0]] == [
        "obs.untraced", "obs.traced.child", "obs.traced"]
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = {ev.name: dict(ev.stats)
              for plane in ProfileData.from_file(path).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for ev in line.events
              if ev.name.startswith("obs.")}
    assert events == {"obs.traced": {"rid": 3, "active": 2},
                      "obs.traced.child": {}}


# ----------------------------------------------------- logging facade
def test_structured_render_format():
    log = get_logger("unit.test", rid=7)
    assert log.render("ev", {}) == "[unit.test] ev rid=7"
    line = log.render("done", {"msg": "two words",
                               "stamp": (3, 0, 9)})
    assert line == '[unit.test] done rid=7 msg="two words" stamp=3/0/9'
    child = log.bind(section="serve")
    assert child.render("ev", {}) == "[unit.test] ev rid=7 section=serve"


def test_configure_is_idempotent_and_message_only(capsys):
    root = logging.getLogger("repro")
    prev = (list(root.handlers), root.propagate, root.level)
    try:
        obs_configure(level="info")
        obs_configure(level="info")      # second call adds no handler
        ours = [h for h in root.handlers
                if getattr(h, "_repro_obs", False)]
        assert len(ours) == 1
        get_logger("unit.test").info("hello", n=3)
        assert "[unit.test] hello n=3" in capsys.readouterr().err
    finally:
        for h in list(root.handlers):
            if getattr(h, "_repro_obs", False):
                root.removeHandler(h)
        root.propagate = prev[1]
        root.setLevel(prev[2])


# ---------------------------------------------------- overhead guard
def test_instrumentation_overhead_bounded():
    """The module-level helpers must stay cheap enough to live on hot
    paths: generous absolute bounds (no cross-timing ratio, which
    flakes on loaded CI machines)."""
    n = 20_000
    reg = metrics.Registry()
    with metrics.use(reg), metrics.label_scope(section="bench"):
        t0 = time.perf_counter()
        for _ in range(n):
            metrics.inc("serve_completed_total")
            metrics.observe("serve_decode_tick_seconds", 0.001)
        enabled = time.perf_counter() - t0
        with metrics.disabled():
            t0 = time.perf_counter()
            for _ in range(n):
                metrics.inc("serve_completed_total")
                metrics.observe("serve_decode_tick_seconds", 0.001)
            off = time.perf_counter() - t0
    # 2 ops per iteration; <50us/op enabled, <5us/op disabled is ~100x
    # headroom over observed cost on a cold CPU container
    assert enabled / (2 * n) < 50e-6
    assert off / (2 * n) < 5e-6
    # disabled() really recorded nothing beyond the enabled loop
    assert report.counter_value(reg.snapshot(), "serve_completed_total",
                                section="bench") == n


# -------------------------------------------------- snapshot loading
def test_load_snapshot_accepts_bare_and_wrapped(tmp_path):
    reg = metrics.Registry()
    with metrics.use(reg), metrics.label_scope(section="s"):
        metrics.observe("mttr_seconds", 0.5)
    snap = reg.snapshot()
    import json
    bare = tmp_path / "bare.json"
    wrapped = tmp_path / "wrapped.json"
    bare.write_text(json.dumps(snap))
    wrapped.write_text(json.dumps({"metrics": snap, "trace": []}))
    for p in (bare, wrapped):
        loaded = report.load_snapshot(str(p))
        assert report.hist_stats(loaded["metrics"], "mttr_seconds",
                                 section="s")["count"] == 1
