"""Per-request serve tracing + breakdown (PR-16 data-plane flight
instruments): nodelet delta-folds of the engine's profiler snapshot,
phase/token counters and tenant-labeled TTFT/ITL histograms, the
compile-storm and SLO-breach flight-recorder triggers, and the
full-path e2e attribution table with its >=0.9 coverage bar."""

import asyncio
import os
import time

import pytest

import ray_tpu.metrics as metrics
from ray_tpu.core.config import GlobalConfig


# ------------------------------------------------------------ helpers

def _scrape(name, **labels):
    """[(value)] for every exposition line of `name` matching labels."""
    out = []
    for line in metrics.prometheus_text().splitlines():
        if not (line.startswith(name + "{") or
                line.startswith(name + " ")):
            continue
        if all(f'{k}="{v}"' in line for k, v in labels.items()):
            out.append(float(line.rsplit(" ", 1)[1]))
    return out


def _one(name, **labels):
    vals = _scrape(name, **labels)
    return vals[0] if vals else 0.0


class _StubController:
    """Records controller.notify calls (the flight-recorder trigger
    path) without a cluster."""

    def __init__(self):
        self.notified = []

    async def notify(self, op, data=None):
        self.notified.append((op, data))
        return True


def _bare_nodelet(controller=None):
    """A Nodelet with ONLY the serve-metrics fold state — the same
    fabrication idiom as test_serve_autoscale's prefix-fold test: the
    handler under test never touches the rest of the object."""
    from ray_tpu.core.nodelet import Nodelet
    n = object.__new__(Nodelet)
    n._serve_counter_seen = {}
    n._compile_events = {}
    n._slo_samples = {}
    n._serve_tenants = set()
    n.controller = controller or _StubController()
    return n


def _fold(n, payload):
    from ray_tpu.core.nodelet import Nodelet
    loop = asyncio.new_event_loop()
    try:
        loop.run_until_complete(
            Nodelet._h_serve_metrics(n, None, payload))
    finally:
        loop.close()


# ----------------------------------------- device-profile fold (units)

def test_nodelet_folds_device_profile_deltas_and_mfu():
    """Profiler snapshots travel CUMULATIVE; the nodelet must inc the
    positive per-(replica, program) delta into the device counters,
    treat a shrink as an engine restart, and set the MFU gauge to the
    latest ratio."""
    n = _bare_nodelet()
    dep = "bd_dp_fold"

    def row(dispatches, device_s, compile_s, compiles, mfu):
        return {"program": "decode_step", "dispatches": dispatches,
                "wall_s": device_s, "device_s": device_s,
                "compile_s": compile_s, "compiles": compiles,
                "shapes": compiles, "tokens": 100, "mfu": mfu}

    base = _one("ray_tpu_device_dispatches_total", deployment=dep,
                program="decode_step")
    push = lambda r: _fold(n, {"deployment": dep, "replica": "r0",
                               "occupied": 1, "waiting": 0,
                               "max_slots": 8, "device_profile": [r]})
    push(row(100, 1.0, 0.2, 2, 0.25))
    assert _one("ray_tpu_device_dispatches_total", deployment=dep,
                program="decode_step") == base + 100
    assert _one("ray_tpu_device_compiles_total", deployment=dep,
                program="decode_step") >= 2
    assert _one("ray_tpu_mfu_ratio", deployment=dep,
                program="decode_step") == 0.25
    push(row(150, 1.5, 0.2, 3, 0.3))        # cumulative growth: +50
    assert _one("ray_tpu_device_dispatches_total", deployment=dep,
                program="decode_step") == base + 150
    assert _one("ray_tpu_mfu_ratio", deployment=dep,
                program="decode_step") == 0.3
    push(row(150, 1.5, 0.2, 3, 0.3))        # no growth: +0
    assert _one("ray_tpu_device_dispatches_total", deployment=dep,
                program="decode_step") == base + 150
    push(row(40, 0.4, 0.1, 1, 0.2))         # shrank: engine restart
    assert _one("ray_tpu_device_dispatches_total", deployment=dep,
                program="decode_step") == base + 190
    secs = _one("ray_tpu_device_seconds_total", deployment=dep,
                program="decode_step")
    assert secs == pytest.approx(1.9)       # 1.0 + 0.5 + restart 0.4


def test_nodelet_folds_phases_tokens_and_shapes():
    n = _bare_nodelet()
    dep = "bd_ph_fold"
    tok0 = _one("ray_tpu_serve_tokens_total", deployment=dep)
    _fold(n, {"deployment": dep, "replica": "r0", "occupied": 0,
              "waiting": 0, "max_slots": 8, "tokens": 40,
              "distinct_program_shapes": 4,
              "phase_totals": {"queue": 0.5, "admission": 0.25,
                               "prefill": 1.0, "decode_dispatch": 2.0}})
    assert _one("ray_tpu_serve_tokens_total", deployment=dep) \
        == tok0 + 40
    assert _one("ray_tpu_serve_program_shapes", deployment=dep,
                replica="r0") == 4.0
    assert _one("ray_tpu_serve_phase_seconds_total", deployment=dep,
                phase="decode_dispatch") == pytest.approx(2.0)
    _fold(n, {"deployment": dep, "replica": "r0", "occupied": 0,
              "waiting": 0, "max_slots": 8, "tokens": 70,
              "distinct_program_shapes": 5,
              "phase_totals": {"queue": 0.5, "admission": 0.25,
                               "prefill": 1.5, "decode_dispatch": 3.5}})
    assert _one("ray_tpu_serve_tokens_total", deployment=dep) \
        == tok0 + 70
    assert _one("ray_tpu_serve_program_shapes", deployment=dep,
                replica="r0") == 5.0
    assert _one("ray_tpu_serve_phase_seconds_total", deployment=dep,
                phase="decode_dispatch") == pytest.approx(3.5)
    assert _one("ray_tpu_serve_phase_seconds_total", deployment=dep,
                phase="queue") == pytest.approx(0.5)


# ------------------------------------- latency fold + tenant label cap

def test_proxy_latency_fold_labels_tenant_and_caps_cardinality(
        monkeypatch):
    monkeypatch.setitem(GlobalConfig._values,
                        "serve_tenant_label_max", 2)
    n = _bare_nodelet()
    dep = "bd_tenant"
    for tenant in ("alpha", "beta", "gamma", "delta"):
        _fold(n, {"deployment": dep, "tenant": tenant,
                  "ttft_s": 0.05, "itl_s": [0.01, 0.012]})
    for tenant in ("alpha", "beta"):
        assert _one("ray_tpu_serve_ttft_seconds_count",
                    deployment=dep, tenant=tenant) == 1.0
        assert _one("ray_tpu_serve_itl_seconds_count",
                    deployment=dep, tenant=tenant) == 2.0
    # past the cap every new tenant folds into the overflow label
    assert _one("ray_tpu_serve_ttft_seconds_count",
                deployment=dep, tenant="other") == 2.0
    assert not _scrape("ray_tpu_serve_ttft_seconds_count",
                       deployment=dep, tenant="gamma")


# --------------------------------------------- flight-recorder triggers

def test_compile_storm_trigger_fires_past_threshold():
    """Default knobs: >=8 recompiles inside a 30s sliding window on one
    (deployment, replica) must fire ONE `debug_capture` notify with the
    compile_storm trigger — and the window re-arms after firing."""
    ctl = _StubController()
    n = _bare_nodelet(ctl)
    dep = "bd_storm"

    def push(compiles):
        _fold(n, {"deployment": dep, "replica": "r0", "occupied": 0,
                  "waiting": 0, "max_slots": 8, "device_profile": [
                      {"program": "decode_step", "dispatches": compiles,
                       "device_s": 0.0, "compile_s": 0.0,
                       "compiles": compiles, "shapes": compiles,
                       "tokens": 0, "mfu": None}]})

    push(3)                         # 3 recompiles: below threshold
    assert not ctl.notified
    push(10)                        # +7 => 10 in window: storm
    assert len(ctl.notified) == 1
    op, data = ctl.notified[0]
    assert op == "debug_capture"
    assert data["trigger"] == "compile_storm"
    assert data["meta"]["deployment"] == dep
    assert data["meta"]["compiles"] >= 8
    push(13)                        # +3 post-fire: window re-armed
    assert len(ctl.notified) == 1


def test_slo_breach_trigger_fires_on_p95_over_bound(monkeypatch):
    monkeypatch.setitem(GlobalConfig._values,
                        "serve_slo_ttft_p95_s", 0.02)
    monkeypatch.setitem(GlobalConfig._values,
                        "serve_slo_min_samples", 10)
    ctl = _StubController()
    n = _bare_nodelet(ctl)
    dep = "bd_slo"
    for _ in range(9):              # under min_samples: armed, silent
        _fold(n, {"deployment": dep, "tenant": "t", "ttft_s": 0.05})
    assert not ctl.notified
    _fold(n, {"deployment": dep, "tenant": "t", "ttft_s": 0.05})
    assert len(ctl.notified) == 1
    op, data = ctl.notified[0]
    assert op == "debug_capture" and data["trigger"] == "slo_breach"
    assert data["meta"]["kind"] == "ttft"
    assert data["meta"]["p95_s"] > 0.02
    # breach cleared the window: needs min_n FRESH samples to refire
    _fold(n, {"deployment": dep, "tenant": "t", "ttft_s": 0.05})
    assert len(ctl.notified) == 1


def test_slo_eval_disabled_by_default(monkeypatch):
    ctl = _StubController()
    n = _bare_nodelet(ctl)
    for _ in range(30):
        _fold(n, {"deployment": "bd_off", "tenant": "t",
                  "ttft_s": 99.0})
    assert not ctl.notified         # both bounds 0.0 => evaluator off


def test_slo_eval_chaos_site_is_known():
    from ray_tpu.util.fault_injection import validate_plan
    assert not validate_plan([{"site": "serve.slo_eval",
                               "action": "error", "match": {"nth": 1}}])
    assert validate_plan([{"site": "serve.slo_eval",
                           "action": "kill_worker"}])


# ----------------------------------------- breakdown reduction (units)

def test_serve_breakdown_reduction_math():
    """state.serve_breakdown() is a pure reduction over the cluster
    scrape: stream_drain is the client-measured remainder of ITL not
    explained by decode dispatches, and coverage is attributed over
    measured.  Feed it a synthetic scrape via the parser it uses."""
    from ray_tpu import state
    text = "\n".join([
        'ray_tpu_serve_phase_seconds_total{deployment="d",'
        'phase="queue"} 0.1',
        'ray_tpu_serve_phase_seconds_total{deployment="d",'
        'phase="admission"} 0.1',
        'ray_tpu_serve_phase_seconds_total{deployment="d",'
        'phase="prefill"} 0.8',
        'ray_tpu_serve_phase_seconds_total{deployment="d",'
        'phase="decode_dispatch"} 3.0',
        'ray_tpu_serve_tokens_total{deployment="d"} 400',
        'ray_tpu_serve_ttft_seconds_sum{deployment="d",'
        'tenant="anon"} 1.0',
        'ray_tpu_serve_ttft_seconds_count{deployment="d",'
        'tenant="anon"} 10',
        'ray_tpu_serve_itl_seconds_sum{deployment="d",'
        'tenant="anon"} 3.5',
        'ray_tpu_mfu_ratio{program="decode_step",deployment="d"} 0.21',
    ])
    samples = state._prom_samples(text)
    assert samples["ray_tpu_serve_tokens_total"][0][1] == 400.0
    orig = state.cluster_metrics_text
    state.cluster_metrics_text = lambda: text
    try:
        table = state.serve_breakdown()
    finally:
        state.cluster_metrics_text = orig
    d = table["deployments"]["d"]
    assert table["phases"] == ["cold_start", "queue", "admission",
                               "prefill", "decode_dispatch",
                               "stream_drain"]
    assert d["phases_s"]["cold_start"] == 0.0   # warm synthetic scrape
    assert d["tokens"] == 400 and d["requests"] == 10
    assert d["measured_s"] == pytest.approx(4.5)     # ttft + itl sums
    # stream_drain = itl remainder over decode dispatch time
    assert d["phases_s"]["stream_drain"] == pytest.approx(0.5)
    assert d["attributed_s"] == pytest.approx(4.5)   # fully explained
    assert d["coverage"] == pytest.approx(1.0)
    assert d["ms_per_token"]["decode_dispatch"] == pytest.approx(7.5)
    assert d["mfu"]["decode_step"] == 0.21


# --------------------------------------------------- full-path e2e

def test_serve_breakdown_end_to_end(tmp_path):
    """The acceptance path: streamed generation through proxy → router
    → replica engine on the CPU harness; the attribution table must
    explain >=90% of client-measured serve time, the tenant label must
    ride the rid propagation into the TTFT/ITL histograms, the folded
    program-shapes gauge must agree with the engine's own stats, MFU
    gauges must be live — and a pushed recompile storm must land a
    compile_storm flight bundle on disk."""
    requests = pytest.importorskip("requests")
    import jax.numpy as jnp

    import ray_tpu
    from ray_tpu import serve, state
    from ray_tpu.models import TransformerConfig
    dump_dir = str(tmp_path / "incidents")
    os.environ["RAY_TPU_FLIGHT_RECORDER_DIR"] = dump_dir
    # the CPU has no published peak, so no mfu gauge of its own: give the
    # fold a declared one to divide by
    ray_tpu.init(num_cpus=4,
                 system_config={"device_profile_peak_flops": 2e11})
    try:
        serve.start()

        @serve.deployment(max_concurrent_queries=8)
        class Generator:
            def __init__(self):
                from ray_tpu.serve.decode_session import \
                    DecodeSessionCore
                self.core = DecodeSessionCore(
                    TransformerConfig.tiny(max_seq_len=256,
                                           dtype=jnp.float32),
                    max_len=256)

            def __call__(self, req):
                return self.core.handle(req)

        serve.run(Generator.bind(), name="generate")
        addr = serve.api.http_address()
        http = requests.Session()

        def stream_one(i, tenant=None, header=None):
            body = {"prompt": [(7 * i + j) % 250 for j in range(32)],
                    "max_new_tokens": 12}
            if tenant:
                body["tenant"] = tenant
            n = 0
            with http.post(f"{addr}/generate/stream", json=body,
                           headers=({"x-tenant": header} if header
                                    else None),
                           stream=True, timeout=120) as r:
                r.raise_for_status()
                for line in r.iter_lines():
                    if line.startswith(b"data: ") and b"token" in line:
                        n += 1
            return n

        stream_one(0)                       # warmup compiles
        total = 0
        for i in range(1, 7):
            total += stream_one(i, tenant=f"team-{i % 2}")
        total += stream_one(7, header="hdr-tenant")
        assert total > 0
        time.sleep(1.5)     # final 0.5s-cadence engine push + fold

        table = state.serve_breakdown()
        dep = table["deployments"]["generate"]
        assert dep["tokens"] > 0 and dep["requests"] >= 7
        assert set(dep["phases_s"]) == set(table["phases"])
        # the acceptance bar: the instruments explain >=90% of what
        # streaming clients measured end to end
        assert dep["coverage"] is not None and dep["coverage"] >= 0.9

        text = state.cluster_metrics_text()
        # tenant labels: request-field AND x-tenant-header lanes
        assert 'tenant="team-0"' in text and 'tenant="team-1"' in text
        assert 'tenant="hdr-tenant"' in text
        # per-program MFU gauges folded cluster-wide
        assert 'ray_tpu_mfu_ratio{program="decode_step"' in text \
            or 'ray_tpu_mfu_ratio{deployment="generate"' in text
        # exposition stays lintable with the new families live
        assert metrics.lint_registry() == []

        # program-shapes gauge == the engine's own ledger (consistency)
        st = http.post(f"{addr}/generate",
                       json={"op": "stats"}, timeout=30).json()
        want = float(st["engine"]["distinct_program_shapes"])
        got = [
            (tags, v) for tags, v in state._prom_samples(text).get(
                "ray_tpu_serve_program_shapes", [])
            if tags.get("deployment") == "generate"]
        assert got and got[0][1] == want

        # pushed recompile storm -> compile_storm bundle on disk (the
        # nodelet's sliding-window detector + controller capture)
        nodes = [r for r in state.list_nodes() if r.get("alive")]
        assert nodes
        addr0 = nodes[0]["addr"]
        for cum in (2, 20):     # delta 18 >= default threshold 8
            state._node_call(addr0, "serve_metrics", {
                "deployment": "stormy", "replica": "r9",
                "occupied": 0, "waiting": 0, "max_slots": 8,
                "device_profile": [
                    {"program": "decode_step", "dispatches": cum,
                     "device_s": 0.0, "compile_s": 0.5 * cum,
                     "compiles": cum, "shapes": cum, "tokens": 0,
                     "mfu": None}]})

        deadline = time.monotonic() + 20.0
        bundles = []
        while time.monotonic() < deadline:
            bundles = [b for b in (os.listdir(dump_dir)
                                   if os.path.isdir(dump_dir) else [])
                       if "compile_storm" in b]
            if bundles:
                break
            time.sleep(0.25)
        assert bundles, "compile storm must capture a flight bundle"
        serve.shutdown()
    finally:
        os.environ.pop("RAY_TPU_FLIGHT_RECORDER_DIR", None)
        ray_tpu.shutdown()
        from ray_tpu.core.config import GlobalConfig
        GlobalConfig.update({"device_profile_peak_flops": 0.0},
                            export_env=False)
        os.environ.pop("RAY_TPU_DEVICE_PROFILE_PEAK_FLOPS", None)
