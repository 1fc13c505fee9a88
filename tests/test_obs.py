"""Observability stack: tracer, metrics registry, communication ledger, and
their wiring through the solver + serving layers.

Covers the PR-7 acceptance criteria: the disabled tracer is an identity
no-op with no per-call retention, the enabled tracer stays within a
per-span overhead budget, Chrome-trace export round-trips through JSON with
the schema Perfetto expects, and — the load-bearing one — per-iteration
bytes measured by the ``CommLedger`` from the REAL transports match the
analytic counts derived independently from the graph topology, for both
the dense reference transport and the SPMD ring.
"""

import json
import threading
import time
import tracemalloc
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import KernelSpec, build_setup, oos, solver
from repro.core.solver import run_chunked
from repro.core.topology import ring
from repro.data import kpca_dataset, node_dataset
from repro.obs import metrics, trace
from repro.obs.comm import CommLedger, CommProfile
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import NOOP_SPAN, Tracer
from repro.serve.batching import PER_REQUEST_WINDOW, EngineStats, RequestStats
from repro.serve.kpca_engine import KpcaEngine, KpcaServeConfig
from repro.serve.publisher import ModelHandle, stream_chunks

SPEC = KernelSpec(kind="rbf", gamma=None)


@pytest.fixture(autouse=True)
def _no_global_tracer():
    """Tests must not leak an enabled process-wide tracer."""
    yield
    trace.disable()


# ---------------------------------------------------------------------------
# tracer


class TestTracer:
    def test_span_records_duration_and_attrs(self):
        t = Tracer()
        with t.span("work", n=3):
            # a real measurable duration is the POINT of this test
            time.sleep(0.002)  # repro-lint: disable=sleep-in-test
        (ev,) = t.events()
        ph, name, t0, dur, tid, attrs = ev
        assert (ph, name) == ("X", "work")
        assert dur >= 2e6                    # >= 2ms in ns
        assert attrs == {"n": 3}
        assert tid == threading.get_ident()

    def test_span_records_on_exception_path(self):
        t = Tracer()
        with pytest.raises(RuntimeError):
            with t.span("boom"):
                raise RuntimeError("x")
        assert [e[1] for e in t.events()] == ["boom"]

    def test_annotate_mid_span(self):
        t = Tracer()
        with t.span("s") as s:
            s.annotate(rows=7)
        assert t.events()[0][5] == {"rows": 7}

    def test_ring_keeps_latest_and_counts_drops(self):
        t = Tracer(capacity=4)
        for i in range(10):
            t.instant(f"e{i}")
        assert t.n_recorded == 10 and t.n_dropped == 6
        assert [e[1] for e in t.events()] == ["e6", "e7", "e8", "e9"]

    def test_complete_backdates(self):
        t = Tracer()
        t.complete("queue_wait", 0.5, rid=1)
        (ev,) = t.events()
        assert ev[0] == "X" and ev[3] == int(0.5e9)

    def test_durations_filters_by_name(self):
        t = Tracer()
        with t.span("a"):
            pass
        t.complete("b", 0.25)
        t.instant("a")                       # instants are not durations
        assert t.durations("b") == [0.25]
        assert len(t.durations("a")) == 1

    def test_capacity_validation(self):
        with pytest.raises(ValueError):
            Tracer(capacity=0)

    def test_threads_record_concurrently(self):
        t = Tracer()
        gate = threading.Barrier(4)  # all alive at once, so tids differ

        def worker():
            gate.wait()
            for _ in range(200):
                with t.span("w"):
                    pass

        ts = [threading.Thread(target=worker) for _ in range(4)]
        for th in ts:
            th.start()
        for th in ts:
            th.join()
        assert t.n_recorded == 800
        tids = {e[4] for e in t.events()}
        assert len(tids) == 4


class TestDisabledTracerIsFree:
    def test_identity_noop_singleton(self):
        trace.disable()
        # deliberate unentered spans — the identity check IS the test
        # repro-lint: disable=span-not-closed
        assert trace.span("hot") is NOOP_SPAN
        assert trace.span("other", a=1) is NOOP_SPAN  # repro-lint: disable=span-not-closed
        assert not trace.is_enabled() and trace.active() is None
        trace.instant("nothing")             # no-ops, no error
        trace.complete("nothing", 1.0)

    def test_no_per_call_retention(self):
        trace.disable()
        with trace.span("warm"):             # warm any lazy interning
            pass
        tracemalloc.start()
        base = tracemalloc.take_snapshot()
        for _ in range(5000):
            with trace.span("hot"):
                pass
        snap = tracemalloc.take_snapshot()
        tracemalloc.stop()
        stats = snap.compare_to(base, "filename")
        grown = sum(s.size_diff for s in stats if s.size_diff > 0)
        # 5000 disabled spans must retain nothing (tracemalloc's own
        # bookkeeping noise stays far under this bound; a single retained
        # span per call would blow it by orders of magnitude)
        assert grown < 64 * 1024, f"retained {grown} bytes"

    def test_export_raises_while_disabled(self):
        trace.disable()
        with pytest.raises(RuntimeError):
            trace.export("/dev/null")

    def test_fault_paths_allocate_nothing_while_disabled(self):
        """The fault-injection layer must be observability-free when
        tracing is off: FaultyComm censoring and the engine's
        retry/recovery loop emit through pre-created module-level
        counters and ``is_enabled()``-guarded trace calls — no per-call
        metric creation, no span retention."""
        from repro.data import kpca_dataset
        from repro.faults import FaultyComm, transient_faults
        from repro.serve import KpcaEngine, KpcaServeConfig, ModelHandle

        trace.disable()
        src = np.array([[0, 1], [1, 0]], np.int32)
        comm = FaultyComm(solver.DenseComm(src, np.zeros((2, 2), np.int32)),
                          jnp.ones((2, 2), jnp.float32))
        cols = jnp.ones((2, 2, 3), jnp.float32)
        model = oos.fit_central(jnp.asarray(kpca_dataset(24, m=6, seed=0)),
                                KernelSpec(kind="rbf"), n_components=2)

        def retry_once():
            eng = KpcaEngine(
                ModelHandle(model),
                KpcaServeConfig(max_batch=8, min_bucket=8, max_retries=2,
                                retry_backoff_s=0.0),
                inject_fault=transient_faults(1))
            eng.submit(np.zeros((2, 6), np.float32))
            eng.flush()

        comm.exchange(cols)                  # warm lazy jit/interning
        retry_once()
        keys_before = len(metrics.snapshot())
        tracemalloc.start()
        base = tracemalloc.take_snapshot()
        for _ in range(200):
            comm.exchange(cols)
        retry_once()
        snap = tracemalloc.take_snapshot()
        tracemalloc.stop()
        assert len(metrics.snapshot()) == keys_before  # no new metric keys
        stats = snap.compare_to(base, "filename")
        grown = sum(s.size_diff for s in stats
                    if s.size_diff > 0
                    and ("/obs/" in (s.traceback[0].filename or "")
                         or "/faults/" in (s.traceback[0].filename or "")))
        assert grown < 16 * 1024, f"obs/faults retained {grown} bytes"


class TestEnabledTracerBudget:
    def test_per_span_overhead_budget(self):
        n = 20_000
        t = trace.enable(capacity=1024)
        t0 = time.perf_counter()
        for _ in range(n):
            with trace.span("bench"):
                pass
        per_span = (time.perf_counter() - t0) / n
        trace.disable()
        assert t.n_recorded == n
        # measured ~2us on CI-class CPUs; 100us still catches a lock
        # convoy or accidental per-span export
        assert per_span < 100e-6, f"{per_span * 1e6:.1f}us per span"

    def test_install_hands_back_prior_tracer_with_events(self):
        outer = trace.enable()
        trace.instant("before")
        inner = Tracer()
        trace.install(inner)
        assert trace.active() is inner
        trace.install(outer)
        assert trace.active() is outer
        assert [e[1] for e in outer.events()] == ["before"]


class TestChromeExport:
    def test_round_trip_schema(self, tmp_path):
        t = Tracer()
        with t.span("phase", rows=3, note="x"):
            time.sleep(0.001)  # repro-lint: disable=sleep-in-test
        t.instant("mark", ok=True)
        path = tmp_path / "trace.json"
        n = t.export(str(path))
        doc = json.loads(path.read_text())
        assert len(doc["traceEvents"]) == n
        assert doc["displayTimeUnit"] == "ms"
        by_ph = {}
        for ev in doc["traceEvents"]:
            by_ph.setdefault(ev["ph"], []).append(ev)
        (meta,) = by_ph["M"]                 # thread_name metadata
        assert meta["name"] == "thread_name"
        (x,) = by_ph["X"]
        assert x["name"] == "phase"
        assert x["dur"] >= 1e3               # microseconds
        assert x["args"] == {"rows": 3, "note": "x"}
        assert {"pid", "tid", "ts"} <= set(x)
        (i,) = by_ph["i"]
        assert i["s"] == "t" and i["args"] == {"ok": True}

    def test_non_json_attrs_stringified(self):
        t = Tracer()
        t.instant("e", arr=np.zeros(2))
        doc = t.to_chrome()
        json.dumps(doc)                      # must not raise
        ev = [e for e in doc["traceEvents"] if e["ph"] == "i"][0]
        assert isinstance(ev["args"]["arr"], str)


# ---------------------------------------------------------------------------
# metrics


class TestMetrics:
    def test_counter_monotonic(self):
        reg = MetricsRegistry()
        c = reg.counter("x_total", "help")
        c.inc()
        c.inc(2.5)
        assert c.value == 3.5
        with pytest.raises(ValueError):
            c.inc(-1)

    def test_gauge_set_and_inc(self):
        reg = MetricsRegistry()
        g = reg.gauge("depth")
        g.set(5)
        g.inc(-2)
        assert g.value == 3.0

    def test_histogram_cumulative_buckets(self):
        reg = MetricsRegistry()
        h = reg.histogram("lat_seconds", buckets=(0.1, 1.0, 10.0))
        h.observe(0.05)
        h.observe_many([0.5, 0.5, 5.0, 50.0])
        snap = h.snapshot()
        assert snap["count"] == 5
        assert snap["sum"] == pytest.approx(56.05)
        assert snap["buckets"] == [[0.1, 1], [1.0, 3], [10.0, 4]]

    def test_histogram_rejects_bad_buckets(self):
        reg = MetricsRegistry()
        with pytest.raises(ValueError):
            reg.histogram("h", buckets=(1.0, 1.0))
        with pytest.raises(ValueError):
            reg.histogram("h2", buckets=())

    def test_get_or_create_identity_and_kind_conflict(self):
        reg = MetricsRegistry()
        a = reg.counter("n_total", label="x")
        b = reg.counter("n_total", label="x")
        c = reg.counter("n_total", label="y")
        assert a is b and a is not c
        with pytest.raises(TypeError):
            reg.gauge("n_total", label="x")

    def test_snapshot_shape_and_json(self):
        reg = MetricsRegistry()
        reg.counter("a_total", "ha").inc(2)
        reg.gauge("b").set(1)
        reg.histogram("c_seconds", buckets=(1.0,)).observe(0.5)
        snap = reg.snapshot()
        json.dumps(snap)
        kinds = {m["name"]: m["kind"] for m in snap["metrics"]}
        assert kinds == {"a_total": "counter", "b": "gauge",
                         "c_seconds": "histogram"}

    def test_write_json(self, tmp_path):
        reg = MetricsRegistry()
        reg.counter("a_total").inc()
        path = tmp_path / "metrics.json"
        reg.write_json(str(path))
        assert json.loads(path.read_text())["metrics"][0]["value"] == 1

    def test_prometheus_text_format(self):
        reg = MetricsRegistry()
        reg.counter("req_total", "requests", transport="ring").inc(3)
        reg.histogram("lat_seconds", "latency", buckets=(0.1, 1.0)) \
            .observe_many([0.05, 0.5])
        text = reg.prometheus_text()
        assert "# HELP req_total requests" in text
        assert "# TYPE req_total counter" in text
        assert 'req_total{transport="ring"} 3' in text
        assert 'lat_seconds_bucket{le="0.1"} 1' in text
        assert 'lat_seconds_bucket{le="+Inf"} 2' in text
        assert "lat_seconds_sum 0.55" in text
        assert "lat_seconds_count 2" in text
        assert text.endswith("\n")

    def test_reset_drops_everything(self):
        reg = MetricsRegistry()
        reg.counter("a_total").inc()
        reg.reset()
        assert reg.snapshot() == {"metrics": []}

    def test_default_registry_helpers_route_to_one_instance(self):
        c = metrics.counter("test_obs_helper_total")
        assert metrics.counter("test_obs_helper_total") is c
        assert any(m["name"] == "test_obs_helper_total"
                   for m in metrics.snapshot()["metrics"])


# ---------------------------------------------------------------------------
# communication ledger


class TestCommLedger:
    def test_routes_setup_vs_iteration(self):
        led = CommLedger()
        led.record_exchange(100, 2)          # before any iteration -> setup
        led.begin_iteration()
        led.record_exchange(10)
        led.record_collective(4)
        led.end_iteration()
        assert led.setup.bytes == 100 and led.setup.messages == 2
        assert led.per_iter.bytes == 10 and led.per_iter.messages == 1
        assert led.per_iter.collectives == 1
        assert led.per_iter.collective_bytes == 4

    def test_totals_scale_by_iterations(self):
        led = CommLedger()
        led.record_exchange(100)
        led.begin_iteration()
        led.record_exchange(10, 3)
        led.end_iteration()
        led.add_iterations(7)
        tot = led.totals()
        assert tot.bytes == 100 + 70
        assert tot.messages == 1 + 21

    def test_snapshot_is_json_ready(self):
        led = CommLedger()
        led.begin_iteration()
        led.record_exchange(8)
        led.end_iteration()
        led.add_iterations(2)
        snap = led.snapshot()
        json.dumps(snap)
        assert snap["iterations"] == 2
        assert snap["totals"]["bytes"] == 16

    def test_profile_scaled(self):
        p = CommProfile(bytes=3, messages=2, collectives=1,
                        collective_bytes=4)
        q = p.scaled(5)
        assert (q.bytes, q.messages, q.collectives, q.collective_bytes) \
            == (15, 10, 5, 20)


def _dense_setup(j=8, n=16, hops=2):
    nodes, _ = node_dataset(n_nodes=j, n_per_node=n, m=12, seed=0)
    return build_setup(jnp.asarray(nodes), ring(j, hops=hops), SPEC)


class TestDenseCommAccounting:
    def test_measured_bytes_match_analytic_count(self):
        """MEASURED: trace-time hooks in DenseComm.exchange during a real
        run. EXPECTED: derived independently from the topology — the ADMM
        step makes 3 exchanges per iteration (alpha, K^-1 B columns,
        z-projections), each moving one fp32 N-vector over every directed
        off-diagonal edge of the neighbor graph, network-wide."""
        j, n, hops = 8, 16, 2
        setup = _dense_setup(j, n, hops)
        led = CommLedger()
        chunks = list(run_chunked(setup, n_iters=6, chunk=3, ledger=led))

        src = np.asarray(setup.src)
        mask = np.asarray(setup.mask)
        own = np.arange(j)[:, None]
        directed_edges = int(np.sum((src != own) & (mask > 0)))
        assert directed_edges == j * 2 * hops          # ring(j, hops)

        expected_per_iter = 3 * directed_edges * n * 4  # fp32
        assert led.per_iter.bytes == expected_per_iter
        assert led.per_iter.messages == 3 * directed_edges
        assert led.iterations == 6
        assert led.totals().bytes == 6 * expected_per_iter
        # every chunk carries its own share
        assert [c.comm_bytes for c in chunks] \
            == [3 * expected_per_iter] * 2
        assert [c.comm_messages for c in chunks] \
            == [3 * 3 * directed_edges] * 2

    def test_no_ledger_means_zero_fields(self):
        setup = _dense_setup()
        chunk = next(iter(run_chunked(setup, n_iters=2, chunk=2)))
        assert chunk.comm_bytes == 0 and chunk.comm_messages == 0

    def test_solver_spans_recorded(self):
        t = trace.enable()
        setup = _dense_setup()
        list(run_chunked(setup, n_iters=4, chunk=2))
        names = {e[1] for e in t.events()}
        trace.disable()
        assert {"solver.step", "solver.rho2"} <= names


@pytest.mark.skipif(jax.device_count() < 4, reason="needs 4 devices")
class TestRingCommAccounting:
    def test_measured_per_node_bytes_match_analytic_count(self):
        """RingComm counts ONE node's egress (SPMD: each device runs the
        same program). Per iteration each node ppermutes one fp32 N-vector
        to each of its 2*hops neighbors, three times, plus one scalar
        psum for the residual."""
        from jax.sharding import Mesh
        from repro.core.dkpca import dkpca_distributed

        j, n, m, hops, iters = 4, 16, 12, 1, 5
        mesh = Mesh(np.array(jax.devices()[:j]).reshape(j, 1),
                    ("data", "model"))
        x = jnp.asarray(node_dataset(n_nodes=j, n_per_node=n, m=m,
                                     seed=1)[0])
        led = CommLedger()
        dkpca_distributed(x, mesh, hops=hops, n_iters=iters, ledger=led)

        expected_per_iter = 3 * (2 * hops) * n * 4      # fp32, per node
        assert led.per_iter.bytes == expected_per_iter
        assert led.per_iter.messages == 3 * (2 * hops)
        assert led.per_iter.collectives == 1            # residual psum
        assert led.iterations == iters
        # setup: raw-data exchange (2*hops X-blocks) + centering sweep
        # (j rotations of X) + m_slots shifts (2*hops N-vectors)
        expected_setup = (2 * hops) * n * m * 4 + j * n * m * 4 \
            + (2 * hops) * n * 4
        assert led.setup.bytes == expected_setup
        # centering pmean + the default Theorem-2 rho2's scalar pmax
        assert led.setup.collectives == 2


# ---------------------------------------------------------------------------
# serving integration


def _engine(n=128, m=16, **cfg_kw):
    x = jnp.asarray(kpca_dataset(n, m=m, seed=0))
    model = oos.fit_central(x, SPEC, n_components=2, center=True)
    return KpcaEngine(model, KpcaServeConfig(
        max_batch=32, min_bucket=8, **cfg_kw)), m


class TestEngineObservability:
    def test_drain_phases_and_queue_wait_traced(self):
        eng, m = _engine()
        t = trace.enable()
        rng = np.random.default_rng(0)
        futs = [eng.submit(rng.normal(size=(q, m)).astype(np.float32))
                for q in (3, 5, 2)]
        eng.flush()
        for f in futs:
            f.result(timeout=10)
        names = {e[1] for e in t.events()}
        assert {"serve.pack", "serve.dispatch", "serve.device",
                "serve.resolve", "serve.queue_wait"} <= names
        waits = [e for e in t.events() if e[1] == "serve.queue_wait"]
        assert len(waits) == 3
        assert {w[5]["rid"] for w in waits} == {f.request_id for f in futs}
        trace.disable()

    def test_serving_identical_with_tracing_off_and_on(self):
        eng, m = _engine()
        rng = np.random.default_rng(1)
        xq = rng.normal(size=(6, m)).astype(np.float32)
        (off,) = eng.project_many([xq])
        trace.enable()
        (on,) = eng.project_many([xq])
        trace.disable()
        np.testing.assert_array_equal(off, on)

    def test_drain_commits_metrics(self):
        eng, m = _engine()
        before = metrics.counter("serve_requests_total").value
        before_q = metrics.counter("serve_queries_total").value
        rng = np.random.default_rng(2)
        eng.project_many([rng.normal(size=(4, m)).astype(np.float32),
                          rng.normal(size=(7, m)).astype(np.float32)])
        assert metrics.counter("serve_requests_total").value == before + 2
        assert metrics.counter("serve_queries_total").value == before_q + 11
        assert metrics.gauge("serve_queue_depth_rows").value == 0


class TestBoundedPerRequest:
    def test_window_is_bounded(self):
        st = EngineStats()
        for i in range(PER_REQUEST_WINDOW + 100):
            st.per_request.append(RequestStats(i, 1, float(i)))
        assert len(st.per_request) == PER_REQUEST_WINDOW
        # oldest-first eviction: the ring holds the most recent window
        assert st.per_request[0].request_id == 100
        assert st.per_request[-1].request_id == PER_REQUEST_WINDOW + 99

    def test_percentiles_over_window(self):
        st = EngineStats()
        for i in range(PER_REQUEST_WINDOW + 500):
            st.per_request.append(RequestStats(i, 1, 1.0))
        p50, p99 = st.latency_percentiles()
        assert p50 == p99 == 1.0
        assert st.latency_percentiles(qs=(0,)) == (1.0,)

    def test_empty_window_is_zero(self):
        assert EngineStats().latency_percentiles() == (0.0, 0.0)


class TestRefreshDecisionMetrics:
    @staticmethod
    def _chunk(residual, t):
        return solver.ChunkResult(
            state=SimpleNamespace(alpha=np.zeros(3), t=t),
            alpha_hist=None, lagrangian=None,
            primal_residual=np.asarray([residual], np.float32),
            rho_hist=None)

    def test_fire_and_censor_counters(self):
        fired = metrics.counter("solver_refresh_fired_total",
                                policy="EveryK")
        censored = metrics.counter("solver_refresh_censored_total",
                                   policy="EveryK")
        f0, c0 = fired.value, censored.value

        published = []
        handle = SimpleNamespace(refresh=lambda a: published.append(a))
        chunks = [self._chunk(1.0, t) for t in (2, 4, 6, 8, 10)]
        stream_chunks(iter(chunks), handle, every=2)
        # EveryK(2): fires on chunks 2 and 4; chunks 1/3/5 censored, the
        # trailing pending chunk still publishes (not a policy decision)
        assert fired.value - f0 == 2
        assert censored.value - c0 == 3
        assert len(published) == 3

    def test_decisions_traced_with_policy_label(self):
        t = trace.enable()
        handle = SimpleNamespace(refresh=lambda a: None)
        stream_chunks(iter([self._chunk(1.0, 3)]), handle, every=1)
        evs = [e for e in t.events() if e[1] == "solver.refresh_decision"]
        trace.disable()
        assert len(evs) == 1
        assert evs[0][5] == {"fired": True, "policy": "EveryK", "t": 3}


class TestModelHandleObservability:
    def test_publish_swap_traced_and_counted(self):
        x = jnp.asarray(kpca_dataset(64, m=8, seed=0))
        model = oos.fit_central(x, SPEC, n_components=2, center=True)
        handle = ModelHandle(model)
        before = metrics.counter("publish_swaps_total").value
        t = trace.enable()
        v = handle.publish(model)
        trace.disable()
        assert v == 1
        assert metrics.counter("publish_swaps_total").value == before + 1
        evs = [e for e in t.events() if e[1] == "publish.swap"]
        assert evs and evs[0][5]["version"] == 1
