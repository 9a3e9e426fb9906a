"""LiveQuery serving plane (data_accelerator_tpu/lq/): multi-tenant
sessions, micro-batched dispatch, warm-kernel residency.

The load-bearing proofs:

- **Coalescing invariant** (the PR's acceptance criterion): 256
  concurrent sessions with the same compile signature produce exactly
  ONE compiled kernel entry (jit-cache size bounded by the pow2 bucket
  lattice, asserted flat while QPS scales), with per-tenant results
  golden-equal to serial ``KernelService.execute`` — including under
  injected mid-tick kernel failure.
- **No-dispatch-on-reject** (mirror of the fleet gate's no-Popen
  proof): a quota-rejected execute never reaches the coalescer, so it
  can never consume a device dispatch; the REST surface returns 429
  with ``Retry-After`` and a typed JSON body.
- **Shared registry**: the legacy ``KernelService`` and the serving
  plane run on ONE ``SessionManager`` — REST-created kernels are
  TTL-reaped on every access path (the PR's session-leak fix).
"""

import json
import time
from concurrent.futures import ThreadPoolExecutor

import pytest

from data_accelerator_tpu.lq.coalescer import DispatchCoalescer
from data_accelerator_tpu.lq.service import LiveQueryService
from data_accelerator_tpu.lq.session import (
    AdmissionRejected,
    LEGACY_TENANT,
    SessionManager,
)
from data_accelerator_tpu.lq.warmcache import (
    WarmKernelCache,
    signature_for,
)
from data_accelerator_tpu.serve.livequery import Kernel, KernelService

SCHEMA = json.dumps({"type": "struct", "fields": [
    {"name": "deviceId", "type": "long", "nullable": False, "metadata": {}},
    {"name": "temperature", "type": "double", "nullable": False,
     "metadata": {}},
    {"name": "eventTimeStamp", "type": "timestamp", "nullable": False,
     "metadata": {}},
]})
BASE = 1_700_000_000_000
QUERY = (
    "Agg = SELECT deviceId, COUNT(*) AS Cnt, MAX(temperature) AS MaxTemp "
    "FROM DataXProcessedInput GROUP BY deviceId"
)


def rows_for(n=5, key=0):
    return [
        {"deviceId": (i + key) % 7, "temperature": 20.0 + ((i + key) % 13),
         "eventTimeStamp": BASE + i}
        for i in range(n)
    ]


def serial_golden(rows, query=QUERY, max_rows=100):
    """The per-tenant ground truth: one legacy kernel, one execute."""
    svc = KernelService()
    kid = svc.create_kernel("LQFlow", SCHEMA, sample_rows=rows)
    return svc.execute(kid, query, max_rows)


# ---------------------------------------------------------------------------
# SessionManager: quotas, TTL, typed rejections
# ---------------------------------------------------------------------------
class TestSessionManager:
    def test_tenant_session_quota_rejects_typed(self):
        mgr = SessionManager(tenant_max_sessions=2)
        mgr.create("t1", "F")
        mgr.create("t1", "F")
        with pytest.raises(AdmissionRejected) as ei:
            mgr.create("t1", "F")
        assert ei.value.kind == "tenant-sessions"
        assert ei.value.tenant == "t1"
        assert ei.value.retry_after_s > 0
        body = ei.value.to_dict()
        assert body["kind"] == "tenant-sessions"
        assert body["retryAfterSeconds"] > 0
        # other tenants unaffected
        mgr.create("t2", "F")
        assert mgr.stats()["rejected"]["tenant-sessions"] == 1

    def test_service_session_cap_rejects(self):
        mgr = SessionManager(max_sessions=2, tenant_max_sessions=10)
        mgr.create("a", "F")
        mgr.create("b", "F")
        with pytest.raises(AdmissionRejected) as ei:
            mgr.create("c", "F")
        assert ei.value.kind == "service-sessions"
        assert mgr.stats()["rejectedTotal"] == 1

    def test_qps_quota_rejects_with_retry_hint(self):
        clock = [1000.0]
        mgr = SessionManager(tenant_max_qps=2.0, now_fn=lambda: clock[0])
        s = mgr.create("t", "F")
        # burst = max(1, rate) = 2 tokens
        mgr.admit_execute(s)
        mgr.admit_execute(s)
        with pytest.raises(AdmissionRejected) as ei:
            mgr.admit_execute(s)
        assert ei.value.kind == "tenant-qps"
        assert 0 < ei.value.retry_after_s <= 1.0
        assert mgr.stats()["rejected"]["tenant-qps"] == 1

    def test_ttl_reaps_on_every_access_path(self):
        clock = [0.0]
        mgr = SessionManager(ttl_s=10.0, now_fn=lambda: clock[0])
        s = mgr.create("t", "F")
        clock[0] = 11.0
        assert mgr.list() == []  # list reaps — no create needed
        with pytest.raises(KeyError):
            mgr.get(s.id)
        assert mgr.stats()["reaped"] == 1
        assert mgr.stats()["sessions"] == 0

    def test_touch_keeps_session_alive(self):
        clock = [0.0]
        mgr = SessionManager(ttl_s=10.0, now_fn=lambda: clock[0])
        s = mgr.create("t", "F")
        clock[0] = 8.0
        mgr.get(s.id)  # touch
        clock[0] = 16.0
        assert mgr.get(s.id).id == s.id  # 8 s idle < ttl

    def test_legacy_evict_on_full_policy(self):
        clock = [0.0]
        mgr = SessionManager(now_fn=lambda: clock[0])
        a = mgr.create(LEGACY_TENANT, "F", evict_on_full=True, cap=2)
        clock[0] = 1.0
        b = mgr.create(LEGACY_TENANT, "F", evict_on_full=True, cap=2)
        clock[0] = 2.0
        c = mgr.create(LEGACY_TENANT, "F", evict_on_full=True, cap=2)
        ids = {s.id for s in mgr.list(tenant=LEGACY_TENANT)}
        assert ids == {b.id, c.id}  # oldest evicted, no rejection
        assert a.id not in ids
        assert mgr.stats()["rejectedTotal"] == 0


# ---------------------------------------------------------------------------
# The coalescing invariant (acceptance criterion)
# ---------------------------------------------------------------------------
class TestCoalescingInvariant:
    def test_256_sessions_one_compiled_entry_golden_equal(self):
        """256 concurrent same-signature sessions -> ONE compiled
        kernel entry (<= the lattice prediction of 1 signature), one
        jitted-step cache entry, and per-tenant results golden-equal to
        serial KernelService.execute. Repeated rounds scale QPS while
        the cache size stays flat."""
        rows = rows_for(50)
        golden = serial_golden(rows)
        lq = LiveQueryService()
        sids = [
            lq.create_session(f"tenant-{i}", "LQFlow", SCHEMA,
                              sample_rows=rows)["id"]
            for i in range(256)
        ]
        # the sessions all share one compile signature: the lattice
        # predicts exactly ONE kernel entry for this load
        sessions = [lq.sessions.get(sid) for sid in sids]
        sigs = {
            signature_for(s, QUERY).key
            for s in sessions
        }
        assert len(sigs) == 1

        cache_sizes = []
        for _round in range(3):  # QPS scales; compile surface must not
            pendings = [
                lq.coalescer.submit(lq.sessions.get(sid), QUERY)
                for sid in sids
            ]
            lq.coalescer.flush()
            results = [p.wait(30.0) for p in pendings]
            for r in results:
                assert r["result"] == golden["result"]
                assert r["headers"] == golden["headers"]
            cache_sizes.append(
                (len(lq.cache), lq.cache.step_cache_entries())
            )
        # jit-cache surface bounded by the lattice, flat across rounds
        assert cache_sizes == [(1, 1)] * 3
        st = lq.coalescer.stats()
        # identical payloads coalesce to ONE dispatch per round
        assert st["dispatches"] == 3
        assert st["calls"] == 3 * 256
        assert st["coalesced"] == 3 * 256 - 3
        lq.stop()

    def test_distinct_payloads_share_compiled_entry(self):
        """Sessions with DIFFERENT sample rows in the same pow2 bucket
        share the compiled kernel (no retrace) but each gets its own
        golden-equal result."""
        lq = LiveQueryService()
        variants = [rows_for(40 + i, key=i) for i in range(4)]
        sids = [
            lq.create_session(f"t{i}", "LQFlow", SCHEMA,
                              sample_rows=v)["id"]
            for i, v in enumerate(variants)
        ]
        pendings = [
            lq.coalescer.submit(lq.sessions.get(sid), QUERY)
            for sid in sids
        ]
        lq.coalescer.flush()
        for v, p in zip(variants, pendings):
            assert p.wait(30.0)["result"] == serial_golden(v)["result"]
        # 4 distinct payloads -> 4 dispatches, but ONE compiled entry:
        # every row count pads into the same 64-row bucket
        st = lq.coalescer.stats()
        assert st["dispatches"] == 4
        assert len(lq.cache) == 1
        assert lq.cache.step_cache_entries() == 1
        lq.stop()

    def test_bucket_lattice_bounds_entries(self):
        """Row counts in different pow2 buckets are different
        signatures — entries == lattice prediction, not session
        count."""
        lq = LiveQueryService()
        small = rows_for(10)    # bucket 64
        large = rows_for(100)   # bucket 128
        for i in range(6):
            sid = lq.create_session(
                f"t{i}", "LQFlow", SCHEMA,
                sample_rows=small if i % 2 else large,
            )["id"]
            lq.execute(sid, QUERY)
        assert len(lq.cache) == 2  # exactly the two buckets
        lq.stop()

    def test_concurrent_ticker_load_golden_and_flat_cache(self):
        """Threaded executes through the ticker'd service: results stay
        golden, compile surface stays one entry."""
        rows = rows_for(30)
        golden = serial_golden(rows)
        lq = LiveQueryService(ticker=True, conf={
            "datax.job.process.lq.maxbatchwaitms": "4",
            "datax.job.process.lq.tenant.maxqps": "100000",
            "datax.job.process.lq.tenant.maxsessions": "64",
            "datax.job.process.lq.maxsessions": "4096",
        })
        sids = [
            lq.create_session(f"t{i % 8}", "LQFlow", SCHEMA,
                              sample_rows=rows)["id"]
            for i in range(32)
        ]
        with ThreadPoolExecutor(16) as ex:
            results = list(ex.map(
                lambda sid: lq.execute(sid, QUERY), sids * 4
            ))
        assert all(r["result"] == golden["result"] for r in results)
        assert len(lq.cache) == 1
        assert lq.cache.step_cache_entries() == 1
        st = lq.coalescer.stats()
        assert st["coalesced"] > 0  # micro-batching actually happened
        lq.stop()

    def test_mid_tick_kernel_failure_isolated_and_recovers(self, monkeypatch):
        """A kernel failure mid-tick fails ONLY the raising payload's
        callers; other tenants in the same dispatch group still get
        golden results, and the next tick re-warms the signature."""
        good_rows = rows_for(20)
        bad_rows = [
            {"deviceId": 999, "temperature": 1.0, "eventTimeStamp": BASE}
        ] + rows_for(19)
        golden = serial_golden(good_rows)

        orig = Kernel.execute

        def boom(self, query, max_rows=100):
            if self.sample_rows and self.sample_rows[0]["deviceId"] == 999:
                raise RuntimeError("injected mid-tick kernel failure")
            return orig(self, query, max_rows)

        monkeypatch.setattr(Kernel, "execute", boom)
        lq = LiveQueryService()
        good = [
            lq.create_session(f"g{i}", "LQFlow", SCHEMA,
                              sample_rows=good_rows)["id"]
            for i in range(3)
        ]
        bad = lq.create_session("b", "LQFlow", SCHEMA,
                                sample_rows=bad_rows)["id"]
        pendings = {
            sid: lq.coalescer.submit(lq.sessions.get(sid), QUERY)
            for sid in good + [bad]
        }
        lq.coalescer.flush()  # ONE dispatch group, mixed payloads
        for sid in good:
            assert pendings[sid].wait(30.0)["result"] == golden["result"]
        with pytest.raises(RuntimeError, match="injected"):
            pendings[bad].wait(30.0)
        assert lq.coalescer.stats()["failedDispatches"] == 1
        # the poisoned entry was dropped; the next tick re-warms and
        # serves (through the persistent compile cache in production)
        p = lq.coalescer.submit(lq.sessions.get(good[0]), QUERY)
        lq.coalescer.flush()
        assert p.wait(30.0)["result"] == golden["result"]
        assert lq.cache.rewarms == 1
        lq.stop()


# ---------------------------------------------------------------------------
# WarmKernelCache: modeled budget, evictions, re-warm
# ---------------------------------------------------------------------------
class TestWarmKernelCache:
    def _entry(self, lq, n_rows, query=QUERY, key=0):
        sid = lq.create_session(f"t{n_rows}-{key}", "LQFlow", SCHEMA,
                                sample_rows=rows_for(n_rows, key=key))["id"]
        lq.execute(sid, query)
        return sid

    def test_entries_priced_by_model(self):
        lq = LiveQueryService()
        self._entry(lq, 10)
        entry = next(iter(lq.cache._entries.values()))
        assert entry.sized_by == "model"
        assert entry.hbm_bytes > 0
        lq.stop()

    def test_budget_eviction_counted_lru(self):
        lq = LiveQueryService(conf={
            # 1 MB budget: the second kernel must evict the first
            # once both are priced (each is small but the budget
            # is enforced against the modeled sum)
            "datax.job.process.lq.hbmbudgetmb": "1",
        })
        # shrink the budget below two entries' fallback/model price
        lq.cache.budget_bytes = 6000
        self._entry(lq, 10)
        first_key = next(iter(lq.cache._entries))
        self._entry(lq, 100)  # different bucket -> second entry
        assert lq.cache.evictions >= 1
        assert first_key not in lq.cache._entries  # LRU went first
        assert lq.cache.resident_bytes() <= max(
            lq.cache.budget_bytes,
            max(e.hbm_bytes for e in lq.cache._entries.values()),
        )
        lq.stop()

    def test_rewarm_counted_on_readmit(self):
        lq = LiveQueryService()
        lq.cache.budget_bytes = 6000
        sid_small = self._entry(lq, 10)
        self._entry(lq, 100)  # evicts the small bucket's kernel
        assert lq.cache.evictions >= 1
        lq.execute(sid_small, QUERY)  # re-admit -> re-warm
        assert lq.cache.rewarms == 1
        lq.stop()

    def test_evict_flow_drops_resident_kernels(self):
        lq = LiveQueryService()
        self._entry(lq, 10)
        assert len(lq.cache) == 1
        assert lq.cache.evict_flow("LQFlow") == 1
        assert len(lq.cache) == 0
        lq.stop()


# ---------------------------------------------------------------------------
# Quota rejection never dispatches (the no-Popen mirror)
# ---------------------------------------------------------------------------
class TestNoDispatchOnReject:
    def test_rejected_execute_never_reaches_coalescer(self, monkeypatch):
        lq = LiveQueryService(conf={
            "datax.job.process.lq.tenant.maxqps": "1",
        })
        sid = lq.create_session("t", "LQFlow", SCHEMA,
                                sample_rows=rows_for(5))["id"]
        lq.execute(sid, QUERY)  # consumes the single-token burst
        dispatches_before = lq.coalescer.stats()["dispatches"]

        def no_submit(*a, **k):
            raise AssertionError("coalescer.submit called for a "
                                 "quota-rejected execute")

        monkeypatch.setattr(lq.coalescer, "submit", no_submit)
        for _ in range(3):
            with pytest.raises(AdmissionRejected) as ei:
                lq.execute(sid, QUERY)
            assert ei.value.kind == "tenant-qps"
        assert lq.coalescer.stats()["dispatches"] == dispatches_before
        assert lq.sessions.stats()["rejected"]["tenant-qps"] == 3
        assert lq.lq_metrics()["LQ_Admission_Rejected_Count"] == 3.0
        lq.stop()


# ---------------------------------------------------------------------------
# REST surface: routes, 429 + Retry-After, shared registry
# ---------------------------------------------------------------------------
@pytest.fixture
def api(tmp_path):
    from data_accelerator_tpu.serve.flowservice import FlowOperation
    from data_accelerator_tpu.serve.restapi import DataXApi
    from data_accelerator_tpu.serve.storage import (
        LocalDesignTimeStorage,
        LocalRuntimeStorage,
    )

    flow_ops = FlowOperation(
        LocalDesignTimeStorage(str(tmp_path / "design")),
        LocalRuntimeStorage(str(tmp_path / "runtime")),
    )
    return DataXApi(flow_ops)


class TestRestSurface:
    def _create(self, api, tenant="alice", rows=None):
        status, payload = api.dispatch("POST", "lq/session", body={
            "tenant": tenant,
            "name": "LQFlow",
            "inputSchema": SCHEMA,
            "sampleRows": rows or rows_for(5),
        })
        assert status == 200, payload
        return payload["result"]["id"]

    def test_session_create_execute_close_roundtrip(self, api):
        sid = self._create(api)
        status, payload = api.dispatch("POST", "lq/execute", body={
            "sessionId": sid, "query": QUERY,
        })
        assert status == 200
        assert payload["result"]["result"] == serial_golden(
            rows_for(5))["result"]
        status, payload = api.dispatch("GET", "lq/sessions")
        assert status == 200
        assert [s["id"] for s in payload["result"]] == [sid]
        status, payload = api.dispatch("POST", "lq/session/close", body={
            "sessionId": sid,
        })
        assert status == 200 and payload["result"]["closed"] is True
        status, _ = api.dispatch("POST", "lq/execute", body={
            "sessionId": sid, "query": QUERY,
        })
        assert status == 404  # closed session is gone

    def test_quota_rejection_is_429_typed_no_dispatch(self, api, monkeypatch):
        api.livequery.sessions.tenant_max_sessions = 1
        self._create(api, tenant="bob")
        dispatches = api.livequery.coalescer.stats()["dispatches"]
        status, payload = api.dispatch("POST", "lq/session", body={
            "tenant": "bob", "name": "LQFlow", "inputSchema": SCHEMA,
            "sampleRows": rows_for(5),
        })
        assert status == 429
        err = payload["error"]
        assert err["kind"] == "tenant-sessions"
        assert err["tenant"] == "bob"
        assert err["retryAfterSeconds"] > 0
        assert api.livequery.coalescer.stats()["dispatches"] == dispatches
        # execute-path rejection: no coalescer call at all
        api.livequery.sessions.tenant_max_qps = 1.0
        sid = self._create(api, tenant="carol")
        st, _ = api.dispatch("POST", "lq/execute",
                             body={"sessionId": sid, "query": QUERY})
        assert st == 200  # burst token
        monkeypatch.setattr(
            api.livequery.coalescer, "submit",
            lambda *a, **k: pytest.fail("dispatch on rejected execute"),
        )
        st, payload = api.dispatch("POST", "lq/execute",
                                   body={"sessionId": sid, "query": QUERY})
        assert st == 429
        assert payload["error"]["kind"] == "tenant-qps"

    def test_retry_after_header_over_http(self, api):
        import urllib.request

        from data_accelerator_tpu.serve.restapi import DataXApiService

        api.livequery.sessions.tenant_max_sessions = 1
        svc = DataXApiService(api, port=0)
        svc.start()
        try:
            def post(body):
                req = urllib.request.Request(
                    f"http://127.0.0.1:{svc.port}/api/lq/session",
                    data=json.dumps(body).encode(), method="POST",
                    headers={"Content-Type": "application/json"},
                )
                try:
                    with urllib.request.urlopen(req) as resp:
                        return resp.status, dict(resp.headers), json.loads(
                            resp.read())
                except urllib.error.HTTPError as e:
                    return e.code, dict(e.headers), json.loads(e.read())

            body = {"tenant": "dave", "name": "LQFlow",
                    "inputSchema": SCHEMA, "sampleRows": rows_for(5)}
            status, _, _ = post(body)
            assert status == 200
            status, headers, payload = post(body)
            assert status == 429
            assert int(headers["Retry-After"]) >= 1
            assert payload["error"]["kind"] == "tenant-sessions"
        finally:
            svc.stop()

    def test_legacy_kernels_and_lq_sessions_share_one_registry(self, api):
        _, payload = api.dispatch("POST", "kernel", body={
            "name": "LQFlow", "inputSchema": SCHEMA,
            "sampleRows": rows_for(5),
        })
        kid = payload["result"]["kernelId"]
        sid = self._create(api)
        assert api.kernels.sessions is api.livequery.sessions
        assert api.kernels.sessions.stats()["sessions"] == 2
        # the lq listing excludes nothing per tenant filter; the legacy
        # kernel stays invisible to the serving plane's tenant listing
        lq_ids = {s["id"] for s in api.livequery.list_sessions()}
        assert sid in lq_ids and kid in lq_ids  # one registry, all visible

    def test_rest_created_kernel_is_ttl_reaped_without_create(self, api):
        """The legacy session leak: kernels created via REST used to be
        reaped only inside the NEXT create. Now any access path reaps."""
        _, payload = api.dispatch("POST", "kernel", body={
            "name": "LQFlow", "inputSchema": SCHEMA,
            "sampleRows": rows_for(5),
        })
        kid = payload["result"]["kernelId"]
        mgr = api.kernels.sessions
        mgr.ttl_s = 0.01
        time.sleep(0.05)
        status, payload = api.dispatch("GET", "kernels/list")
        assert status == 200 and payload["result"] == []
        assert mgr.stats()["reaped"] == 1
        status, _ = api.dispatch(
            "POST", "kernel/executequery",
            body={"kernelId": kid, "query": QUERY},
        )
        assert status == 404

    def test_flow_delete_cascades_lq_sessions(self, api):
        sid = self._create(api)
        _, payload = api.dispatch("POST", "lq/execute", body={
            "sessionId": sid, "query": QUERY,
        })
        assert len(api.livequery.cache) == 1
        api.livequery.close_flow("LQFlow")
        assert api.livequery.list_sessions() == []
        assert len(api.livequery.cache) == 0

    def test_stats_route_exposes_backlog_signal(self, api):
        self._create(api)
        status, payload = api.dispatch("GET", "lq/stats")
        assert status == 200
        snap = payload["result"]
        assert "LQ_Backlog" in snap["metrics"]
        assert snap["metrics"]["LQ_Sessions"] == 1.0
        assert snap["sessions"]["tenants"] == 1


# ---------------------------------------------------------------------------
# Conf plumbing + designer knobs + alert rule
# ---------------------------------------------------------------------------
class TestConfAndAlerts:
    def test_service_reads_lq_conf_block(self):
        from data_accelerator_tpu.core.config import SettingDictionary

        conf = SettingDictionary({
            "datax.job.process.lq.maxbatchwaitms": "16",
            "datax.job.process.lq.maxfanin": "32",
            "datax.job.process.lq.sessionttlseconds": "60",
            "datax.job.process.lq.maxsessions": "99",
            "datax.job.process.lq.tenant.maxsessions": "3",
            "datax.job.process.lq.tenant.maxqps": "7.5",
            "datax.job.process.lq.hbmbudgetmb": "256",
        })
        lq = LiveQueryService(conf=conf)
        assert lq.max_wait_ms == 16.0
        assert lq.coalescer.max_fanin == 32
        assert lq.sessions.ttl_s == 60.0
        assert lq.sessions.max_sessions == 99
        assert lq.sessions.tenant_max_sessions == 3
        assert lq.sessions.tenant_max_qps == 7.5
        assert lq.cache.budget_bytes == 256 * 1024 * 1024
        assert not lq.ticking
        lq.stop()

    def test_default_budget_comes_from_cost_model(self):
        from data_accelerator_tpu.analysis.costmodel import (
            warm_kernel_cache_budget_bytes,
        )
        from data_accelerator_tpu.analysis.fleetcheck import (
            DEFAULT_HBM_PER_CHIP,
        )

        lq = LiveQueryService()
        assert lq.cache.budget_bytes == warm_kernel_cache_budget_bytes()
        assert 0 < lq.cache.budget_bytes < DEFAULT_HBM_PER_CHIP
        lq.stop()

    def test_generation_maps_designer_lq_knobs(self, tmp_path):
        from data_accelerator_tpu.core.config import parse_conf_lines
        from data_accelerator_tpu.serve.flowservice import FlowOperation
        from data_accelerator_tpu.serve.storage import (
            LocalDesignTimeStorage,
            LocalRuntimeStorage,
        )
        from test_serve_generation import make_gui

        fo = FlowOperation(
            LocalDesignTimeStorage(str(tmp_path / "d")),
            LocalRuntimeStorage(str(tmp_path / "r")),
            fleet_admission=False,
        )
        gui = make_gui("lqknobs")
        gui["process"]["jobconfig"].update({
            "jobLqMaxBatchWaitMs": "12",
            "jobLqTenantMaxSessions": "5",
            "jobLqTenantMaxQps": "25",
            "jobLqHbmBudgetMb": "512",
        })
        fo.save_flow(gui)
        res = fo.generate_configs("lqknobs")
        assert res.ok, res.errors
        props = parse_conf_lines(
            open(res.conf_paths[0], encoding="utf-8").readlines()
        )
        assert props["datax.job.process.lq.maxbatchwaitms"] == "12"
        assert props["datax.job.process.lq.tenant.maxsessions"] == "5"
        assert props["datax.job.process.lq.tenant.maxqps"] == "25"
        assert props["datax.job.process.lq.hbmbudgetmb"] == "512"
        # a serving plane built from the generated conf honors them
        from data_accelerator_tpu.core.config import SettingDictionary

        lq = LiveQueryService(conf=SettingDictionary(dict(props)))
        assert lq.max_wait_ms == 12.0
        assert lq.sessions.tenant_max_qps == 25.0
        lq.stop()

    def test_lq_latency_slo_default_rule(self):
        from data_accelerator_tpu.constants import MetricName
        from data_accelerator_tpu.obs.alerts import (
            default_rules,
            validate_rules,
        )

        rules = default_rules("AnyFlow")
        assert validate_rules(rules) == []
        by_name = {r["name"]: r for r in rules}
        rule = by_name["lq-latency-slo"]
        assert rule["metric"] == "Latency-LQExec-p99"
        assert rule["action"] == "backpressure"  # pilot-visible vote
        assert MetricName.is_runtime_metric(rule["metric"])
        # the alert engine resolves the series through the live
        # histogram via the lq-exec stage (constants.MetricName.STAGES)
        assert "lq-exec" in MetricName.STAGES
        assert MetricName.stage_metric("lq-exec") == "Latency-LQExec"

    def test_lq_alert_fires_on_slow_exec_histogram(self):
        """End to end: a slow LQExec histogram drives the default rule
        to firing with the backpressure action attached."""
        from data_accelerator_tpu.obs.alerts import AlertEngine, default_rules
        from data_accelerator_tpu.obs.histogram import HistogramRegistry

        hist = HistogramRegistry()
        for _ in range(50):
            hist.observe("LiveQuery", "lq-exec", 5000.0)
        clock = [1000.0]
        eng = AlertEngine(
            [r for r in default_rules() if r["name"] == "lq-latency-slo"],
            flow="LiveQuery", histograms=hist, now_fn=lambda: clock[0],
        )
        assert eng.evaluate() == []  # pending (forSeconds)
        clock[0] += 30.0
        firing = eng.evaluate()
        assert [f["name"] for f in firing] == ["lq-latency-slo"]
        assert firing[0]["action"] == "backpressure"


# ---------------------------------------------------------------------------
# Observability: every emitted LQ series resolves through the registry
# ---------------------------------------------------------------------------
class TestObservability:
    def test_exported_metrics_all_registered(self):
        from data_accelerator_tpu.constants import MetricName
        from data_accelerator_tpu.obs.store import MetricStore

        store = MetricStore()
        lq = LiveQueryService(store=store)
        sid = lq.create_session("t", "LQFlow", SCHEMA,
                                sample_rows=rows_for(5))["id"]
        lq.execute(sid, QUERY)
        lq.export_metrics()
        keys = store.keys("DATAX-LiveQuery:")
        assert keys
        unregistered = sorted(
            k.partition(":")[2] for k in keys
            if not MetricName.is_runtime_metric(k.partition(":")[2])
        )
        assert not unregistered, unregistered
        names = {k.partition(":")[2] for k in keys}
        for required in (
            "LQ_Sessions", "LQ_Qps", "LQ_Backlog", "LQ_CoalesceFanin",
            "LQ_Dispatch_Count", "LQ_KernelEvict_Count",
            "LQ_Admission_Rejected_Count", "Latency-LQExec-p99",
        ):
            assert required in names, required
        lq.stop()

    def test_exec_histogram_carries_session_exemplar(self):
        from data_accelerator_tpu.lq.service import LQ_EXEC_STAGE, LQ_FLOW

        lq = LiveQueryService()
        sid = lq.create_session("t", "LQFlow", SCHEMA,
                                sample_rows=rows_for(5))["id"]
        lq.execute(sid, QUERY)
        ex = lq.histograms.get(LQ_FLOW, LQ_EXEC_STAGE).exemplar()
        assert ex is not None and ex["traceId"] == sid
        lq.stop()

    def test_closed_session_cancels_queued_calls(self):
        lq = LiveQueryService()  # tickless: nothing drains the queue
        sid = lq.create_session("t", "LQFlow", SCHEMA,
                                sample_rows=rows_for(5))["id"]
        pending = lq.coalescer.submit(lq.sessions.get(sid), QUERY)
        assert lq.coalescer.backlog() == 1
        lq.close_session(sid)
        assert lq.coalescer.backlog() == 0
        with pytest.raises(RuntimeError, match="closed before"):
            pending.wait(0.5)
        lq.stop()
