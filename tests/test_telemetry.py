"""Continuous telemetry tier (automerge_tpu/obs/telemetry.py, obs/prom.py,
service lag probes + describe/scrape — INTERNALS §14, ISSUE 9).

The contracts under test:

- **Emit-time exactness.** Telemetry-backed span/counter aggregates stay
  EXACT under forced trace-ring wraparound, while the retained-record
  view demonstrably diverges — the regression pin for the bug class
  this tier closes (`metrics_snapshot` histograms silently going
  inexact once the ring wrapped).
- **Bounded rolling windows.** The time-series ring holds at most
  `n_windows` windows; ancient windows roll off, totals don't.
- **Prometheus exposition.** `render`ed pages pass the format validator
  (TYPE-declared families, cumulative histogram buckets ending at +Inf
  and equal to `_count`); malformed pages are rejected; the stdlib
  scrape endpoint serves /metrics and /describe over real HTTP.
- **Replication-lag probes.** A tenant whose frames sit un-acked (or
  whose believed clock trails the room head) reports nonzero lag in
  ops and ticks; catching up returns it to zero; peaks are recorded.
- **Black-box postmortem.** `SyncService.describe()` JSON-round-trips
  with health-ladder states, budget/credit occupancy, the lag table,
  and the bounded degradation-event ring — with tracing OFF.
- **Nearest-rank percentiles** in `SyncService.metrics()`.
- **SLO gate** (benchmarks/slo_gate.py): regressions vs the committed
  session rows are detected; single-row groups seed, missing fields
  are reported.
"""

import json
import struct
import threading
import urllib.request
from collections import deque

import pytest

import automerge_tpu as am
from automerge_tpu import Text, obs
from automerge_tpu.obs import prom
from automerge_tpu.obs.recorder import span_totals
from automerge_tpu.obs.telemetry import (BUCKET_LOW, N_BUCKETS, Telemetry,
                                         bucket_index, bucket_le_ns)
from automerge_tpu.service import ServiceConfig, SyncService, TenantBudget
from automerge_tpu.sync import Connection, DocSet


@pytest.fixture(autouse=True)
def _tracing_off():
    obs.disable()
    obs.clear()
    yield
    obs.disable()


# ---------------------------------------------------------------------------
# the telemetry store
# ---------------------------------------------------------------------------


class TestTelemetryStore:
    def test_counters_and_span_aggregates_are_exact(self):
        tel = Telemetry()
        for i in range(100):
            tel.observe_count("svc", "shed", 2)
            tel.observe_span("svc", "tick", 1000 + i)
        assert tel.counters()[("svc", "shed")] == 200
        agg = tel.span_aggregates()[("svc", "tick")]
        assert agg["count"] == 100
        assert agg["total_ns"] == sum(1000 + i for i in range(100))
        assert agg["min_ns"] == 1000 and agg["max_ns"] == 1099

    def test_window_ring_is_bounded_and_rolls(self):
        tel = Telemetry(window_ns=100, n_windows=4)
        for w in range(32):        # 32 distinct windows through a 4-ring
            tel.observe_count("c", "n", 1, ts_ns=w * 100)
        wins = tel.windows()
        assert len(wins) <= 4                       # bounded
        assert [w["window"] for w in wins] == [28, 29, 30, 31]  # newest
        assert tel.counters()[("c", "n")] == 32     # totals never decay
        series = tel.series("c", "n")
        assert all(v == 1 for _, v in series)

    def test_stale_slots_roll_off_the_view(self):
        # a slot never reused keeps its old window in the ring — the
        # read side must drop anything more than one ring span behind
        # the newest, or series rates divide by a bogus horizon
        tel = Telemetry(window_ns=100, n_windows=4)
        tel.observe_count("c", "n", 1, ts_ns=100)       # wid 1, slot 1
        tel.observe_count("c", "n", 1, ts_ns=50_000)    # wid 500, slot 0
        assert [w["window"] for w in tel.windows()] == [500]
        assert tel.counters()[("c", "n")] == 2          # totals intact

    def test_stale_observation_never_clobbers_a_live_window(self):
        # an observation whose ts_ns is older than the whole ring (e.g.
        # a span longer than n_windows*window_ns landing with its START
        # timestamp) must be dropped from the window view — overwriting
        # the live slot would discard that window's accumulated deltas.
        # Exact aggregates still count it.
        tel = Telemetry(window_ns=100, n_windows=4)
        tel.observe_count("c", "n", 5, ts_ns=1050)      # wid 10, slot 2
        tel.observe_span("c", "s", 10, ts_ns=650)       # wid 6, slot 2
        wins = tel.windows()
        assert [w["window"] for w in wins] == [10]      # live slot kept
        assert wins[0]["counters"][("c", "n")] == 5     # delta intact
        assert tel.counters()[("c", "n")] == 5
        assert tel.span_view()[1][("c", "s")]["count"] == 1  # still exact

    def test_power_of_two_duration_lands_in_its_le_bucket(self):
        # inclusive-le semantics: 2^k ns belongs to the le=2^k bucket
        assert bucket_index(1 << BUCKET_LOW) == 0
        assert bucket_le_ns(bucket_index(2048)) == 2048.0
        tel = Telemetry()
        for _ in range(10):
            tel.observe_span("svc", "tick", 2048)
        assert tel.quantile_ns("svc", "tick", 0.99) == 2048.0

    def test_histogram_buckets_and_quantile_bound(self):
        tel = Telemetry()
        durs = [500, 2_000, 2_000, 1_000_000, 60_000_000_000]
        for d in durs:
            tel.observe_span("svc", "tick", d)
        hist = tel.histograms()[("svc", "tick")]
        assert sum(hist) == len(durs)
        assert hist[bucket_index(500)] >= 1
        assert hist[N_BUCKETS] == 1                 # 60 s -> overflow
        # conservative p50: upper edge of the bucket holding rank 3
        q50 = tel.quantile_ns("svc", "tick", 0.50)
        assert 2_000 <= q50 <= 4_096
        # p99 lands in the overflow bucket -> the exact tracked max
        assert tel.quantile_ns("svc", "tick", 0.99) == 60_000_000_000
        assert bucket_le_ns(N_BUCKETS) == float("inf")

    def test_gauges_last_value_wins_and_drop(self):
        tel = Telemetry()
        tel.set_gauge("lag", 5, tenant="a")
        tel.set_gauge("lag", 7, tenant="a")
        tel.set_gauge("lag", 1, tenant="b")
        g = tel.gauges()
        assert g[("lag", (("tenant", "a"),))] == 7
        tel.drop_gauge("lag", tenant="a")
        assert ("lag", (("tenant", "a"),)) not in tel.gauges()
        assert ("lag", (("tenant", "b"),)) in tel.gauges()

    def test_concurrent_writers_merge_exactly(self):
        tel = Telemetry()
        n_threads, n_each = 8, 500
        start = threading.Barrier(n_threads)

        def writer():
            start.wait()
            for _ in range(n_each):
                tel.observe_count("t", "x")
                tel.observe_span("t", "s", 100)

        threads = [threading.Thread(target=writer)
                   for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert tel.counters()[("t", "x")] == n_threads * n_each
        assert tel.span_aggregates()[("t", "s")]["count"] \
            == n_threads * n_each


# ---------------------------------------------------------------------------
# the ISSUE 9 regression pin: exact after wraparound
# ---------------------------------------------------------------------------


class TestWraparoundExactness:
    def test_span_totals_exact_while_ring_view_diverges(
            self, collector_paused):
        """Force trace-ring wraparound: the telemetry-backed spans in
        metrics_snapshot stay exact; the retained-record derivation
        (the pre-ISSUE-9 source) visibly loses history."""
        n = 1000
        with obs.tracing(capacity=32):      # tiny ring: 32/stripe
            obs.clear()
            for _ in range(n):
                t0 = obs.now()
                obs.span("plan", "prepare_batch", t0)
            snap = obs.metrics_snapshot()
            ring_view = span_totals(obs.snapshot())
        exact = snap["spans"]["plan.prepare_batch"]
        assert exact["count"] == n
        assert exact["total_ns"] >= exact["max_ns"] > 0
        # the old derivation is bounded by ring retention -> diverged
        assert ring_view[("plan", "prepare_batch")]["count"] < n
        assert snap["retained"] < snap["emitted"] == n

    def test_event_counters_flow_into_telemetry_windows(self):
        with obs.tracing(capacity=16):
            obs.clear()
            for _ in range(300):
                obs.event("chaos", "drop")
            tel = obs.telemetry()
            assert tel.counters()[("chaos", "drop")] == 300
            assert sum(v for _, v in tel.series("chaos", "drop")) == 300

    def test_since_ns_query_still_serves_ring_view(self):
        """A windowed metrics_snapshot(since_ns) query falls back to the
        retained records (documented): the telemetry store answers
        whole-session aggregates, the ring answers 'recently'."""
        with obs.tracing(capacity=64):
            obs.clear()
            t0 = obs.now()
            obs.span("plan", "prepare_batch", t0)
            cut = obs.now()
            t0 = obs.now()
            obs.span("plan", "prepare_batch", t0)
            snap = obs.metrics_snapshot(since_ns=cut)
        assert snap["spans"]["plan.prepare_batch"]["count"] == 1


# ---------------------------------------------------------------------------
# Prometheus exposition
# ---------------------------------------------------------------------------


def _fed_telemetry():
    tel = Telemetry()
    for i in range(50):
        tel.observe_span("svc", "tick", 10_000 + i * 1000)
        tel.observe_count("svc", "shed", 1)
    tel.set_gauge("replication_lag_ops_max", 3)
    return tel


class TestPromExposition:
    def test_rendered_page_validates(self):
        page = prom.expose(prom.telemetry_families(_fed_telemetry()))
        counts = prom.validate_prom(page)
        assert counts["families"] >= 3 and counts["samples"] > 10
        assert "amtpu_events_total" in page
        assert 'le="+Inf"' in page and "_bucket" in page

    def test_histogram_buckets_cumulative_and_count_consistent(self):
        page = prom.expose(prom.telemetry_families(_fed_telemetry()))
        buckets = [line for line in page.splitlines()
                   if line.startswith("amtpu_span_seconds_bucket")]
        values = [float(line.rsplit(" ", 1)[1]) for line in buckets]
        assert values == sorted(values)             # cumulative
        count = [line for line in page.splitlines()
                 if line.startswith("amtpu_span_seconds_count")]
        assert float(count[0].rsplit(" ", 1)[1]) == values[-1] == 50

    def test_validator_rejects_malformed_pages(self):
        with pytest.raises(prom.PromValidationError):
            prom.validate_prom("")                  # empty
        with pytest.raises(prom.PromValidationError):
            prom.validate_prom("no_type_metric 1\n")   # undeclared
        bad_hist = ("# TYPE h histogram\n"
                    'h_bucket{le="0.1"} 5\n'
                    'h_bucket{le="+Inf"} 3\n')      # not cumulative
        with pytest.raises(prom.PromValidationError):
            prom.validate_prom(bad_hist)
        no_inf = ("# TYPE h histogram\n"
                  'h_bucket{le="0.1"} 5\n')
        with pytest.raises(prom.PromValidationError):
            prom.validate_prom(no_inf)

    def test_label_escaping_round_trips(self):
        tel = Telemetry()
        tel.set_gauge("g", 1, tenant='we"ird\nname')
        page = prom.expose(prom.telemetry_families(tel))
        prom.validate_prom(page)                    # must still parse

    def test_close_brace_in_label_value_round_trips(self):
        # Label values may legally contain '}' (callers control tenant
        # and room ids); the validator must not stop the label block at
        # the first brace it sees.
        tel = Telemetry()
        tel.set_gauge("g", 3, tenant="a}b", room="r}0")
        page = prom.expose(prom.telemetry_families(tel))
        counts = prom.validate_prom(page)
        assert counts["samples"] >= 1
        assert 'tenant="a}b"' in page

    def test_non_finite_values_render_and_validate(self):
        assert prom._fmt_value(float("nan")) == "NaN"
        assert prom._fmt_value(float("inf")) == "+Inf"
        assert prom._fmt_value(float("-inf")) == "-Inf"
        tel = Telemetry()
        tel.set_gauge("ratio", float("nan"))
        tel.set_gauge("floor", float("-inf"))
        page = prom.expose(prom.telemetry_families(tel))
        prom.validate_prom(page)
        assert "NaN" in page and "-Inf" in page

    def test_negative_exponent_values_validate(self):
        # Sub-1e-4 span totals render as e.g. '7.9763e-05'; the
        # validator must accept negative exponents.
        tel = Telemetry()
        tel.observe_span("svc", "tick", 79_763)     # _sum = 7.9763e-05 s
        page = prom.expose(prom.telemetry_families(tel))
        assert "e-05" in page
        prom.validate_prom(page)
        with pytest.raises(prom.PromValidationError):
            prom.validate_prom("# TYPE g gauge\ng 1e-\n")

    def test_span_view_consistent_under_concurrent_emit(self):
        # telemetry_families reads hist + aggregates via span_view(),
        # one lock pass per stripe: +Inf bucket == _count even while
        # writers keep emitting.
        tel = Telemetry()
        stop = threading.Event()

        def writer():
            while not stop.is_set():
                tel.observe_span("svc", "tick", 50_000)

        threads = [threading.Thread(target=writer) for _ in range(4)]
        for t in threads:
            t.start()
        try:
            for _ in range(200):
                hists, aggs = tel.span_view()
                for key, buckets in hists.items():
                    assert sum(buckets) == aggs[key]["count"]
                page = prom.expose(prom.telemetry_families(tel))
                prom.validate_prom(page)
        finally:
            stop.set()
            for t in threads:
                t.join()


# ---------------------------------------------------------------------------
# service integration: lag probes, describe, scrape, percentiles
# ---------------------------------------------------------------------------


class _Client:
    """Deque-transport tenant (the run_all cfg11 shape): pump() flushes
    both directions; withholding pump_down() leaves server frames
    un-acked — the wire-lag scenario."""

    def __init__(self, svc, tid, room_id, base):
        self.svc, self.tid, self.room_id = svc, tid, room_id
        self.to_server, self.to_client = deque(), deque()
        self.ds = DocSet()
        self.ds.set_doc(room_id,
                        am.apply_changes(am.init(f"c-{tid}"), base))
        svc.connect(tid, room_id, self.to_client.append)
        from automerge_tpu.resilience import ResilientChannel
        self.chan = ResilientChannel(self.to_server.append, None)
        self.conn = Connection(self.ds, self.chan.send)
        self.chan._deliver = self.conn.receive_msg
        self.conn.open()

    def pump_up(self):
        while self.to_server:
            env = self.to_server.popleft()
            sess = self.svc.session(self.tid)
            if sess is not None:
                sess.on_wire(env)

    def pump_down(self):
        while self.to_client:
            self.chan.on_wire(self.to_client.popleft())
        self.chan.tick()

    def pump(self):
        self.pump_up()
        self.pump_down()

    def doc(self):
        return self.ds.get_doc(self.room_id)


def _seed(svc, room_id="r"):
    doc = am.change(am.init("origin"), lambda d: (
        d.__setitem__("t", Text("start")), d.__setitem__("m", {})))
    changes = am.get_all_changes(doc)
    svc.seed_doc(room_id, am.apply_changes(am.init("server"), changes))
    return changes


def _settle(svc, clients, max_ticks=300):
    for _ in range(max_ticks):
        for c in clients:
            c.pump()
        svc.tick()
        if svc.idle() and all(c.chan.idle and not c.to_server
                              and not c.to_client for c in clients):
            return
    raise AssertionError(f"never quiesced: {svc.metrics()}")


class TestReplicationLagProbes:
    def test_withheld_acks_report_wire_lag_then_recover(self):
        svc = SyncService()
        base = _seed(svc)
        a = _Client(svc, "a", "r", base)
        b = _Client(svc, "b", "r", base)
        _settle(svc, [a, b])
        # a edits; b NEVER pumps its downlink -> the server's frames to
        # b sit un-acked in b's server-side channel
        a.ds.set_doc("r", am.change(
            a.doc(), lambda d: d["m"].__setitem__("k", 1)))
        for _ in range(4):
            a.pump()
            b.pump_up()            # acks nothing, receives nothing
            svc.tick()
        lag = svc.replication_lag()
        assert lag["b"]["ops"] >= 1, lag
        assert lag["b"]["wire_ops"] >= 1, lag
        first_ticks = lag["b"]["ticks"]
        assert first_ticks >= 1
        svc.tick()
        assert svc.replication_lag()["b"]["ticks"] > first_ticks
        assert lag["a"]["ops"] == 0                 # per-tenant, not global
        m = svc.metrics()
        assert m["max_lag_ops"] >= 1 and m["lagging_tenants"] == 1
        assert m["peak_lag_ops"] >= 1 and m["peak_lag_ticks"] >= 1
        # recovery: the withheld tenant drains -> lag returns to zero
        _settle(svc, [a, b])
        svc.probe_lag()
        lag = svc.replication_lag()
        assert lag["b"]["ops"] == 0 and lag["b"]["ticks"] == 0
        assert svc.metrics()["peak_lag_ops"] >= 1   # peaks are sticky

    def test_lag_counts_matrix_deficit_for_unsent_changes(self):
        """A tenant that revealed its clock but is owed changes the hub
        has not flushed yet (mid-tick view) shows the matrix component."""
        svc = SyncService()
        base = _seed(svc)
        a = _Client(svc, "a", "r", base)
        _settle(svc, [a])
        room = svc.room("r")
        doc = room.doc_set.get_doc("r")
        with room.hub.batched():     # defer the flush: deficit visible
            room.doc_set.set_doc("r", am.change(
                doc, lambda d: d["m"].__setitem__("x", 1)))
            table = room.hub.replication_lag()
            assert table["a"]["ops"] >= 1
            assert table["a"]["docs"].get("r", 0) >= 1
        _settle(svc, [a])

    def test_probe_disabled_by_config(self):
        svc = SyncService(ServiceConfig(lag_probe_ticks=0))
        base = _seed(svc)
        a = _Client(svc, "a", "r", base)
        for _ in range(3):
            a.pump()
            svc.tick()
        assert svc.stats["peak_lag_ops"] == 0       # never probed


class TestDescribeAndScrape:
    def test_describe_round_trips_with_tracing_off(self):
        assert not obs.ENABLED
        svc = SyncService(ServiceConfig(event_log=8))
        base = _seed(svc)
        a = _Client(svc, "a", "r", base)
        _settle(svc, [a])
        svc.evict("a", reason="test")
        dump = json.loads(json.dumps(svc.describe(), default=str))
        assert dump["schema"] == "amtpu-postmortem-v1"
        assert dump["metrics"]["evictions"] == 1
        assert "a" not in dump["tenants"]           # evicted -> gone
        assert dump["rooms"]["r"]["quarantine"]["parked"] == 0
        kinds = [e["event"] for e in dump["events"]]
        assert "join" in kinds and "evict" in kinds
        # same key name as the soak summary / bench session row
        assert "tick_p99_ms_telemetry" in dump
        assert "lag" in dump and "config" in dump

    def test_describe_tenant_entry_carries_ladder_and_occupancy(self):
        svc = SyncService()
        base = _seed(svc)
        a = _Client(svc, "a", "r", base)
        _settle(svc, [a])
        entry = svc.describe()["tenants"]["a"]
        for key in ("state", "starved_streak", "inbox", "inbox_cap",
                    "in_flight", "recv_buffered", "lag_ops", "lag_ticks",
                    "stats", "channel"):
            assert key in entry, key
        assert entry["state"] == "live"
        assert entry["inbox_cap"] == svc.config.default_budget.inbox_cap

    def test_event_ring_is_bounded(self):
        svc = SyncService(ServiceConfig(event_log=4))
        for i in range(10):
            svc._note("shed", msgs=i)
        assert len(svc.describe()["events"]) == 4
        assert svc.describe()["events"][-1]["msgs"] == 9

    def test_scrape_page_validates_and_carries_lag_series(self):
        svc = SyncService()
        base = _seed(svc)
        a = _Client(svc, "a", "r", base)
        b = _Client(svc, "b", "r", base)
        _settle(svc, [a, b])
        a.ds.set_doc("r", am.change(
            a.doc(), lambda d: d["m"].__setitem__("k", 1)))
        for _ in range(3):
            a.pump()
            b.pump_up()
            svc.tick()
        page = svc.scrape()
        counts = prom.validate_prom(page)
        assert counts["families"] > 10
        assert "amtpu_svc_replication_lag_ops{" in page
        assert 'tenant="b"' in page
        assert "amtpu_svc_span_seconds_bucket" in page   # tick histogram
        _settle(svc, [a, b])

    def test_scrape_bounds_lag_series_to_config(self):
        svc = SyncService(ServiceConfig(prom_lag_series=2))
        base = _seed(svc)
        clients = [_Client(svc, f"t{i}", "r", base) for i in range(5)]
        _settle(svc, clients)
        page = svc.scrape()
        n = sum(1 for line in page.splitlines()
                if line.startswith("amtpu_svc_replication_lag_ops{"))
        assert n <= 2

    def test_http_endpoint_serves_metrics_and_describe(self):
        svc = SyncService()
        base = _seed(svc)
        a = _Client(svc, "a", "r", base)
        _settle(svc, [a])
        srv = svc.serve_metrics()
        try:
            body = urllib.request.urlopen(
                srv.url + "/metrics", timeout=10).read().decode()
            prom.validate_prom(body)
            dump = json.loads(urllib.request.urlopen(
                srv.url + "/describe", timeout=10).read())
            assert dump["schema"] == "amtpu-postmortem-v1"
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(srv.url + "/nope", timeout=10)
        finally:
            srv.close()

    def test_aborted_scrape_is_quiet(self, capfd):
        # a scraper that drops the connection mid-response must not dump a
        # socketserver traceback to stderr (the handler/handle_error guards)
        import socket
        svc = SyncService()
        base = _seed(svc)
        a = _Client(svc, "a", "r", base)
        _settle(svc, [a])
        srv = svc.serve_metrics()
        try:
            for _ in range(5):
                s = socket.create_connection((srv.host, srv.port), timeout=5)
                s.sendall(b"GET /metrics HTTP/1.1\r\nHost: x\r\n\r\n")
                # abort hard (RST) without reading the body
                s.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                             struct.pack("ii", 1, 0))
                s.close()
            # a well-behaved scrape still works afterwards
            body = urllib.request.urlopen(
                srv.url + "/metrics", timeout=10).read().decode()
            prom.validate_prom(body)
        finally:
            srv.close()
        err = capfd.readouterr().err
        assert "Traceback" not in err, err

    def test_obs_telemetry_rides_along_when_tracing(self):
        svc = SyncService()
        base = _seed(svc)
        a = _Client(svc, "a", "r", base)
        with obs.tracing():
            obs.clear()
            _settle(svc, [a])
            page = svc.scrape()
        prom.validate_prom(page)
        assert "amtpu_obs_" in page


class TestMetricsPercentiles:
    def test_nearest_rank_indexing(self):
        svc = SyncService()
        svc._tick_ms.extend(float(i + 1) for i in range(100))  # 1..100
        m = svc.metrics()
        # nearest-rank: p50 of 1..100 is the 50th value, p99 the 99th
        assert m["p50_tick_ms"] == 50.0
        assert m["p99_tick_ms"] == 99.0
        assert m["max_tick_ms"] == 100.0

    def test_single_sample_and_empty(self):
        svc = SyncService()
        assert svc.metrics()["p99_tick_ms"] == 0.0
        svc._tick_ms.append(7.0)
        m = svc.metrics()
        assert m["p50_tick_ms"] == m["p99_tick_ms"] == 7.0

    def test_tick_history_is_bounded(self):
        svc = SyncService(ServiceConfig(tick_ring=16))
        for i in range(100):
            svc._tick_ms.append(float(i))
        assert len(svc._tick_ms) == 16              # deque maxlen


class TestPublicIntrospection:
    def test_hub_peer_state_lifecycle(self):
        ds = DocSet()
        doc = am.change(am.init("o"), lambda d: d.__setitem__("m", {}))
        ds.set_doc("d", doc)
        from automerge_tpu.sync.hub import SyncHub
        hub = SyncHub(ds)
        hub.open()
        hub.add_peer("p", lambda msg: None)
        hub.note_clock("p", "d", {})
        st = hub.peer_state("p")
        assert st["present"] and st["matrix_slot"]
        assert st["revealed_docs"] == 1
        hub.remove_peer("p")
        st = hub.peer_state("p")
        assert not st["present"] and not st["matrix_slot"]
        assert st["revealed_docs"] == st["session_docs"] == 0

    def test_gate_quarantine_items_snapshot(self):
        ds = DocSet()
        from automerge_tpu.resilience.inbound import InboundGate
        gate = InboundGate(ds)
        premature = {"actor": "x", "seq": 5, "deps": {"ghost": 3},
                     "ops": [], "message": ""}
        gate.deliver("doc", [premature], validated=True, sender="tEn")
        items = gate.quarantine_items()
        assert ("doc", "x", 5, "tEn") in items
        assert gate.quarantine_items("doc") == items
        assert gate.quarantine_items("other") == []
        assert gate.evict_sender("tEn") == 1
        assert gate.quarantine_items() == []

    def test_reclaimed_uses_public_surface(self):
        """reclaimed() must agree with the public introspection it now
        reads — evict, then both report clean."""
        svc = SyncService()
        base = _seed(svc)
        _Client(svc, "a", "r", base)
        svc.tick()
        svc.evict("a", reason="test")
        assert svc.reclaimed("a")
        st = svc.room("r").hub.peer_state("a")
        assert not st["present"] and not st["matrix_slot"]
        assert all(s != "a" for *_, s
                   in svc.room("r").gate.quarantine_items())


# ---------------------------------------------------------------------------
# the SLO gate
# ---------------------------------------------------------------------------


def _row(metric, value, platform="cpu", **extra):
    return {"metric": metric, "platform": platform, "value": value,
            **extra}


class TestSloGate:
    def test_throughput_regression_detected(self):
        from benchmarks import slo_gate
        rows = [_row("e2e_pipeline_ops_per_sec", 5_000_000,
                     serial_profile={"prepare_s": 0.02, "commit_s": 0.01}),
                _row("e2e_pipeline_ops_per_sec", 3_000_000,
                     serial_profile={"prepare_s": 0.02, "commit_s": 0.01})]
        findings = slo_gate.check(rows)
        viol = [f for f in findings if f["status"] == "violation"]
        assert any(f["field"] == "value" for f in viol)
        ok = [f for f in findings if f["status"] == "ok"]
        assert any(f["field"] == "serial_profile.prepare_s" for f in ok)

    def test_span_term_regression_detected(self):
        from benchmarks import slo_gate
        rows = [_row("e2e_pipeline_ops_per_sec", 5_000_000,
                     serial_profile={"prepare_s": 0.02, "commit_s": 0.01}),
                _row("e2e_pipeline_ops_per_sec", 5_000_000,
                     serial_profile={"prepare_s": 0.2, "commit_s": 0.01})]
        viol = [f for f in slo_gate.check(rows)
                if f["status"] == "violation"]
        assert any(f["field"] == "serial_profile.prepare_s" for f in viol)

    def test_service_slos_and_derived_shed_rate(self):
        from benchmarks import slo_gate
        rows = [_row("cfg11_service_200_sessions", 300.0, p99_tick_ms=100,
                     shed_total=0, admitted_ops=2000, max_lag_ops=0,
                     max_lag_ticks=0),
                _row("cfg11_service_200_sessions", 290.0, p99_tick_ms=400,
                     shed_total=1500, admitted_ops=2000, max_lag_ops=3,
                     max_lag_ticks=2)]
        findings = slo_gate.check(rows)
        viol = {f["field"] for f in findings if f["status"] == "violation"}
        assert "p99_tick_ms" in viol                # 4x > 1.5x slack
        assert "shed_rate" in viol                  # 0 -> 0.75/op
        assert "max_lag_ops" in viol                # absolute: nonzero
        assert "value" not in viol                  # 290 >= 0.7 * 300

    def test_single_row_seeds_and_missing_field_reported(self):
        from benchmarks import slo_gate
        rows = [_row("cfg11_service_50_sessions", 300.0,
                     shed_total=0, admitted_ops=100, max_lag_ops=0,
                     max_lag_ticks=0)]        # no p99_tick_ms
        findings = slo_gate.check(rows)
        assert any(f["status"] == "missing"
                   and f["field"] == "p99_tick_ms" for f in findings)
        assert all(f["status"] != "violation" for f in findings)

    def test_platforms_never_cross_compare(self):
        from benchmarks import slo_gate
        rows = [_row("e2e_pipeline_ops_per_sec", 100_000_000,
                     platform="tpu"),
                _row("e2e_pipeline_ops_per_sec", 4_000_000,
                     platform="cpu")]
        assert all(f["status"] != "violation"
                   for f in slo_gate.check(rows))

    def test_gate_main_warn_only_exits_zero(self, tmp_path):
        from benchmarks import slo_gate
        log = tmp_path / "sessions.jsonl"
        rows = [_row("e2e_pipeline_ops_per_sec", 5_000_000),
                _row("e2e_pipeline_ops_per_sec", 1_000_000)]
        log.write_text("\n".join(json.dumps(r) for r in rows) + "\n")
        assert slo_gate.main(["--sessions", str(log)]) == 0
        assert slo_gate.main(["--sessions", str(log), "--strict"]) == 1
