"""Continuous-batching serve engine: admission/evict scheduling, per-request
bit-equivalence with single-request reference decode, and mid-stream fault
failover under both modes (dispatcher recompile + resident health mask)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build_model
from repro.serve import (RECOMPILE, RESIDENT, Request, ServeConfig,
                         ServeEngine, reference_decode, reference_margins,
                         synthetic_workload)
from repro.viscosity import INTERPRET, SW

KEY = jax.random.PRNGKey(0)


def _setup(arch="qwen1.5-4b"):
    cfg = get_config(arch).reduced()
    model = build_model(cfg)
    params = model.init(KEY)
    return cfg, params


def _workload(cfg, n, rng, max_prompt=19, max_new=9, arrival_every=2):
    return synthetic_workload(cfg.vocab_size, n, rng, max_prompt=max_prompt,
                              max_new=max_new, arrival_every=arrival_every,
                              per_arrival=2)


# --------------------------------------------------------- fixed-batch API
def test_generate_shapes_and_determinism():
    cfg, params = _setup()
    eng = ServeEngine(cfg, params, ServeConfig(max_len=80))
    prompts = jax.random.randint(KEY, (3, 16), 0, cfg.vocab_size).astype(
        jnp.int32)
    toks1, _ = eng.generate(prompts, 12)
    toks2, _ = eng.generate(prompts, 12)
    assert toks1.shape == (3, 12)
    np.testing.assert_array_equal(toks1, toks2)


def test_fault_midstream_identical_tokens():
    """The paper's functional guarantee, end-to-end on a real LM: a fault
    + reroute mid-generation leaves the decoded tokens unchanged."""
    cfg, params = _setup()
    prompts = jax.random.randint(KEY, (2, 16), 0, cfg.vocab_size).astype(
        jnp.int32)
    eng = ServeEngine(cfg, params, ServeConfig(max_len=80))
    base, _ = eng.generate(prompts, 16)
    eng2 = ServeEngine(cfg, params, ServeConfig(max_len=80))
    faulted, stats = eng2.generate(prompts, 16,
                                   fault_at_step=(8, "flash_attention"))
    np.testing.assert_array_equal(base, faulted)
    assert eng2.fault_state.is_faulty("flash_attention")


def test_fault_midstream_ssm():
    cfg, params = _setup("rwkv6-1.6b")
    prompts = jax.random.randint(KEY, (2, 12), 0, cfg.vocab_size).astype(
        jnp.int32)
    eng = ServeEngine(cfg, params, ServeConfig(max_len=80))
    base, _ = eng.generate(prompts, 8)
    eng2 = ServeEngine(cfg, params, ServeConfig(max_len=80))
    faulted, _ = eng2.generate(prompts, 8, fault_at_step=(4, "rwkv6_wkv"))
    np.testing.assert_array_equal(base, faulted)


# --------------------------------------------------- continuous batching
def test_unequal_lengths_match_reference_decode():
    """Requests of unequal prompt length and budget, decoded together in
    slots, are bit-identical to single-request decode on the bare model."""
    cfg, params = _setup()
    rng = np.random.default_rng(0)
    reqs = _workload(cfg, 6, rng)
    eng = ServeEngine(cfg, params, ServeConfig(max_len=64, max_slots=3))
    done, stats = eng.serve(reqs)
    assert sorted(done) == sorted(r.rid for r in reqs)
    for r in reqs:
        ref = reference_decode(cfg, params, r.prompt, r.max_new_tokens,
                               max_len=64)
        np.testing.assert_array_equal(done[r.rid].tokens, ref)
        assert done[r.rid].prompt_len == len(r.prompt)
        assert len(done[r.rid].tokens) == r.max_new_tokens


def test_staggered_admission_and_slot_reuse():
    """More requests than slots with staggered arrivals: slots are reused
    (continuous batching), nobody is admitted before arrival, and the
    engine ends with everything completed."""
    cfg, params = _setup()
    rng = np.random.default_rng(1)
    reqs = _workload(cfg, 16, rng, arrival_every=3)
    eng = ServeEngine(cfg, params, ServeConfig(max_len=64, max_slots=4))
    done, stats = eng.serve(reqs)
    assert len(done) == 16
    assert stats["admitted"] == 16
    assert max(stats["occupancy"]) <= 4
    for r in reqs:
        assert done[r.rid].admitted_step >= r.arrival
    # with 16 requests on 4 slots the engine must have reused slots
    assert stats["steps"] > max(r.arrival for r in reqs)


@pytest.mark.parametrize("mode", [RECOMPILE, RESIDENT])
def test_fault_mid_decode_completes_in_flight(mode):
    """A stage quarantined while sequences are mid-decode: every in-flight
    request still completes, with outputs bit-identical to the
    single-request reference (and to a fault-free serve)."""
    cfg, params = _setup()
    rng = np.random.default_rng(2)
    reqs = _workload(cfg, 8, rng)
    eng = ServeEngine(cfg, params, ServeConfig(max_len=64, max_slots=3,
                                               failover=mode))
    done, stats = eng.serve(reqs, fault_at_step=(4, "flash_attention"))
    assert len(done) == len(reqs)
    assert eng.fault_state.is_faulty("flash_attention")
    for r in reqs:
        ref = reference_decode(cfg, params, r.prompt, r.max_new_tokens,
                               max_len=64)
        np.testing.assert_array_equal(done[r.rid].tokens, ref)


def test_recompile_mode_reconfigures_once():
    """With a healthy route distinct from the fallback, a fault is exactly
    one reconfiguration (plan-keyed Dispatcher recompile)."""
    cfg, params = _setup()
    rng = np.random.default_rng(3)
    reqs = _workload(cfg, 4, rng, max_new=7)
    eng = ServeEngine(cfg, params, ServeConfig(max_len=64, max_slots=2,
                                               hw_route=INTERPRET,
                                               failover=RECOMPILE))
    done, stats = eng.serve(reqs, fault_at_step=(3, "flash_attention"))
    assert len(done) == len(reqs)
    assert stats["recompiles"] == 1
    assert stats["decode_compiles"] == 2


def test_resident_mode_never_recompiles():
    """Hot-spare residency: the fault flips a health-mask bit; the decode
    executable is compiled exactly once."""
    cfg, params = _setup()
    rng = np.random.default_rng(3)
    reqs = _workload(cfg, 4, rng, max_new=7)
    eng = ServeEngine(cfg, params, ServeConfig(max_len=64, max_slots=2,
                                               hw_route=INTERPRET,
                                               failover=RESIDENT))
    done, stats = eng.serve(reqs, fault_at_step=(3, "flash_attention"))
    assert len(done) == len(reqs)
    assert stats["recompiles"] == 0
    assert stats["decode_compiles"] == 1
    # prefill is resident too: one dispatcher build serves admissions on
    # both sides of the fault (jit re-specializes per prompt length only)
    assert stats["prefill_compiles"] == 1


def test_failover_modes_agree():
    """Same workload, same mid-stream fault: recompile and resident modes
    produce identical tokens (same routing history, two mechanisms)."""
    cfg, params = _setup()
    rng = np.random.default_rng(4)
    reqs = _workload(cfg, 5, rng, max_new=7)
    outs = {}
    for mode in (RECOMPILE, RESIDENT):
        eng = ServeEngine(cfg, params, ServeConfig(max_len=64, max_slots=3,
                                                   hw_route=INTERPRET,
                                                   failover=mode))
        done, _ = eng.serve(reqs, fault_at_step=(3, "flash_attention"))
        outs[mode] = done
    for r in reqs:
        np.testing.assert_array_equal(outs[RECOMPILE][r.rid].tokens,
                                      outs[RESIDENT][r.rid].tokens)


def test_plan_dedupes_identical_routings():
    """When healthy target == fallback, a fault does not change the
    RoutingPlan, so the dispatcher never recompiles — signature-keyed
    caching could not see this."""
    cfg, params = _setup()
    rng = np.random.default_rng(5)
    reqs = _workload(cfg, 3, rng, max_new=6)
    eng = ServeEngine(cfg, params, ServeConfig(max_len=64, max_slots=3,
                                               hw_route=SW))
    done, stats = eng.serve(reqs, fault_at_step=(2, "flash_attention"))
    assert len(done) == len(reqs)
    assert stats["recompiles"] == 0 and stats["decode_compiles"] == 1


def test_serve_is_thin_wrapper_over_session():
    """The closed-loop entry points (serve, generate) are compat
    wrappers over the streaming session API: driving submit/step/poll
    by hand returns bit-identical completions and the same stats."""
    cfg, params = _setup()
    rng = np.random.default_rng(11)
    reqs = _workload(cfg, 8, rng)
    eng = ServeEngine(cfg, params, ServeConfig(max_len=64, max_slots=3))
    done, stats = eng.serve(reqs)

    sess = eng.session()
    for r in sorted(reqs, key=lambda r: (r.arrival, r.rid)):
        sess.submit(r)
    streamed = {}
    while sess.pending():
        sess.step()
        for c in sess.poll():        # poll mid-run: streaming surface
            streamed[c.rid] = c
    sstats = sess.close()
    assert set(streamed) == set(done)
    for rid, c in done.items():
        np.testing.assert_array_equal(c.tokens, streamed[rid].tokens)
        assert c.admitted_step == streamed[rid].admitted_step
        assert c.finished_step == streamed[rid].finished_step
    for k in ("admitted", "steps", "recompiles", "occupancy"):
        assert stats[k] == sstats[k], k
    # prefill_compiles counts per-run jit misses: the serve() run warmed
    # every prompt length, so the session run on the same engine hitting
    # only cache is exactly the shared-dispatcher contract
    assert stats["prefill_compiles"] > 0
    assert sstats["prefill_compiles"] == 0
    # generate() rides the same path
    prompts = np.stack([r.prompt[:6] for r in reqs[:2]])
    toks, _ = eng.generate(prompts, 5)
    done_g, _ = eng.serve([Request(rid=i, prompt=prompts[i],
                                   max_new_tokens=5) for i in range(2)])
    np.testing.assert_array_equal(
        toks, np.stack([done_g[i].tokens for i in range(2)]))


def test_session_steps_emit_the_timed_span_tree():
    """Every engine step leaves a ``serve.step`` span; its admissions and
    its decode tick nest inside it, each with its children in order, and
    children take no longer than their parent.  A tick's ``kv_live`` is
    the positions its slots attend: each admitted request's prompt and
    the tokens it has been served, the newest of which the tick writes."""
    import collections
    import time

    from repro.obs import trace
    cfg, params = _setup()
    reqs = _workload(cfg, 6, np.random.default_rng(5), max_new=4)
    eng = ServeEngine(cfg, params, ServeConfig(max_len=64, max_slots=3))
    sess = eng.session()
    for r in reqs:
        sess.submit(r)
    t0 = time.perf_counter()
    active = []
    while sess.pending():
        active.append(sess.step()["active"])
    sess.close()
    spans, whole = trace.recent_spans(t0)
    assert whole
    kids = collections.defaultdict(list)
    for s in sorted(spans, key=lambda s: s.start):
        kids[s.parent].append(s)
    children = {"serve.admit": ["serve.admit.prefill", "serve.admit.insert",
                                "serve.admit.wait"],
                "serve.tick": ["serve.tick.dispatch", "serve.tick.wait",
                               "serve.tick.fetch", "serve.tick.slots"]}

    def dur(s):
        return s.end - s.start

    steps = kids[-1]
    assert [s.name for s in steps] == ["serve.step"] * len(active)
    assert [s.attrs["step"] for s in steps] == list(range(len(active)))
    admits, live = [], {}     # rid -> (prompt_len, tokens served)
    budget = {r.rid: r.max_new_tokens for r in reqs}
    for s, a in zip(steps, active):
        assert s.attrs["active"] == a
        n = s.attrs["admitted"]
        assert [c.name for c in kids[s.id]] == \
            ["serve.admit"] * n + ["serve.tick"] * (a > 0)
        for c in kids[s.id]:
            assert [g.name for g in kids[c.id]] == children[c.name]
            assert all(not kids[g.id] for g in kids[c.id])
            assert sum(dur(g) for g in kids[c.id]) <= dur(c)
        assert sum(dur(c) for c in kids[s.id]) <= dur(s)
        for c in kids[s.id][:n]:
            if budget[c.attrs["rid"]] > 1:
                live[c.attrs["rid"]] = (c.attrs["prompt_len"], 1)
        if a:
            assert kids[s.id][-1].attrs == {
                "step": s.attrs["step"], "active": a,
                "kv_live": sum(p + k for p, k in live.values())}
            live = {rid: (p, k + 1) for rid, (p, k) in live.items()
                    if k + 1 < budget[rid]}
        admits += kids[s.id][:n]
    assert sorted((c.attrs["rid"], c.attrs["prompt_len"]) for c in admits) \
        == sorted((r.rid, len(r.prompt)) for r in reqs)


def test_request_validation():
    cfg, params = _setup()
    eng = ServeEngine(cfg, params, ServeConfig(max_len=16, max_slots=2))
    too_long = Request(rid=0, prompt=np.zeros(12, np.int32),
                       max_new_tokens=8)
    with pytest.raises(ValueError):
        eng.serve([too_long])
    with pytest.raises(ValueError):   # would otherwise never finish
        eng.serve([Request(rid=0, prompt=np.zeros(4, np.int32),
                           max_new_tokens=0)])
    with pytest.raises(ValueError):   # would otherwise crash inside jit
        eng.serve([Request(rid=0, prompt=np.zeros(0, np.int32),
                           max_new_tokens=2)])
    with pytest.raises(ValueError):   # unknown stage names fail loudly
        eng.inject_fault("warp_core")
    with pytest.raises(ValueError):
        eng.serve([Request(rid=1, prompt=np.zeros(4, np.int32),
                           max_new_tokens=2),
                   Request(rid=1, prompt=np.zeros(4, np.int32),
                           max_new_tokens=2)])
    with pytest.raises(ValueError):
        ServeEngine(cfg, params, ServeConfig(failover="bogus"))
    # every rejection names the offending request id and field
    with pytest.raises(ValueError, match=r"request 3.*field 'deadline'"):
        eng.serve([Request(rid=3, prompt=np.zeros(4, np.int32),
                           max_new_tokens=2, deadline=-1.0)])
    with pytest.raises(ValueError, match=r"request 4.*field 'deadline'.*"
                                         r"expire before it arrives"):
        eng.serve([Request(rid=4, prompt=np.zeros(4, np.int32),
                           max_new_tokens=2, arrival_time=5.0,
                           deadline=2.0)])
    with pytest.raises(ValueError, match=r"request 5.*field "
                                         r"'arrival_time'"):
        eng.serve([Request(rid=5, prompt=np.zeros(4, np.int32),
                           max_new_tokens=2, arrival_time=-0.5)])


def test_reference_margins_zero_on_greedy_path_positive_off_it():
    """Forced along reference_decode's own tokens every margin is zero;
    forcing a different token at one step shows its logit gap there."""
    cfg, params = _setup()
    prompt = np.arange(1, 8, dtype=np.int32)
    ref = reference_decode(cfg, params, prompt, 6, max_len=32)
    np.testing.assert_array_equal(
        reference_margins(cfg, params, prompt, ref, max_len=32),
        np.zeros(6, np.float32))
    off = ref.copy()
    off[2] = (ref[2] + 1) % cfg.vocab_size
    m = reference_margins(cfg, params, prompt, off, max_len=32)
    assert m[2] > 0 and not m[:2].any()

# ------------------------------------------------------- device placement
FLEET_PLACEMENT_SCRIPT = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
import json
import jax
import numpy as np
from repro.configs import get_config
from repro.models import build_model
from repro.serve import (FleetConfig, FleetServeEngine, Request,
                         ServeConfig, ServeEngine)

cfg = get_config("qwen1.5-4b").reduced()
params = build_model(cfg).init(jax.random.PRNGKey(0))
scfg = ServeConfig(max_len=48, max_slots=2)
fleet = FleetServeEngine(cfg, params, scfg,
                         FleetConfig(n_devices=4, n_spares=1))
rng = np.random.default_rng(0)
# every serving slot is full when device 0 fails: its work needs the spare
reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, 5 + i),
                max_new_tokens=8, arrival=0) for i in range(6)]
done, stats = fleet.serve(reqs, events={3: [("device", 0)]})
ref, _ = ServeEngine(cfg, params, scfg).serve(reqs)
print(json.dumps({
    "pool_devices": [d.id for d in fleet.pool_devices],
    "placement": [sorted(d.id for d in w.placement())
                  for w in fleet.workers],
    "per_device_tokens": stats["per_device_tokens"],
    "quarantined": stats["quarantined"],
    "same_tokens": all(np.array_equal(done[r.rid].tokens,
                                      ref[r.rid].tokens) for r in reqs)}))
"""


def test_fleet_pools_sit_on_distinct_devices():
    """With as many devices as fleet slots, every pool's weights and KV
    cache are committed to its own device; a device fault migrates work
    to the spare's device, and tokens equal a one-pool engine's."""
    import json
    import os
    import subprocess
    import sys

    src = os.path.join(os.path.dirname(__file__), "..", "src")
    res = subprocess.run([sys.executable, "-c", FLEET_PLACEMENT_SCRIPT],
                         env=dict(os.environ, PYTHONPATH=src),
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out["pool_devices"] == [0, 1, 2, 3]
    assert out["placement"] == [[0], [1], [2], [3]]
    assert out["quarantined"] == [0]
    assert out["per_device_tokens"][3] > 0
    assert out["same_tokens"]


def test_fleet_larger_than_device_list_is_emulated():
    """A fleet with more slots than the process has devices runs every
    pool on the default device (the single-process benches and tests)."""
    from repro.serve import FleetConfig, FleetServeEngine

    cfg, params = _setup()
    fleet = FleetServeEngine(cfg, params, ServeConfig(max_len=32,
                                                      max_slots=1),
                             FleetConfig(n_devices=len(jax.devices()) + 1))
    assert fleet.pool_devices == [None] * fleet.fcfg.n_devices
    assert all(w.placement() == {jax.devices()[0]} for w in fleet.workers)
