"""SLO engine, critical-path profiler, and health-controller loop
(DESIGN.md §13).

Covers the burn-rate state machine (fire needs BOTH windows, resolve on
short-window recovery), replay/digest determinism, histogram quantile
estimation, critical-path stage attribution, the closed burn→autoscaler
loop (strictly faster recovery with the signal on), the SloConformance
invariant (with tampering negative controls), and the migrated
ExecutorStats registry surface.
"""
import dataclasses

import pytest

from repro.core.batch import ExecutorStats
from repro.obs import (
    AlertEvent,
    BurnRule,
    CriticalPathProfiler,
    HealthController,
    Histogram,
    MetricsRegistry,
    Redactor,
    SloEngine,
    SloSpec,
    Tracer,
    default_burn_rules,
    derive_serve_observations,
    trace_id_for,
)
from repro.sim import (
    ChaosEvent,
    ChaosSchedule,
    CohortArrival,
    BurstyTraffic,
    FleetConfig,
    FleetSim,
    MetricsConservation,
    SloConformance,
)
from repro.utils.timing import SimClock


# one fast rule: long 60s, short 5s, burn >= 2 on both to fire
FAST = (BurnRule(60.0, 5.0, 2.0),)


def _engine(objective=0.5, threshold=1.0, rules=FAST, name="cold_serve"):
    return SloEngine([SloSpec(name, objective=objective, threshold=threshold,
                              rules=rules, budget_window=120.0)])


# ------------------------------------------------------------------ the engine
class TestSloEngine:
    def test_spec_validation(self):
        with pytest.raises(ValueError):
            SloSpec("x", objective=1.0)
        with pytest.raises(ValueError):
            SloSpec("x", rules=())
        with pytest.raises(ValueError):
            BurnRule(5.0, 60.0, 2.0)  # short > long
        with pytest.raises(ValueError):
            BurnRule(60.0, 5.0, 0.0)

    def test_good_bad_judgement_from_threshold(self):
        eng = _engine(threshold=1.0)
        assert eng.observe("cold_serve", t=0.0, value=0.5) is True
        assert eng.observe("cold_serve", t=1.0, value=1.5) is False
        with pytest.raises(ValueError):
            eng.observe("cold_serve", t=2.0)  # neither value nor good

    def test_fire_requires_both_windows(self):
        # objective 0.5 -> burn = bad_frac / 0.5. A single bad blip inside
        # the short window must NOT page while the long window is healthy.
        eng = _engine()
        for t in range(50):
            eng.observe("cold_serve", t=float(t), value=0.1)
        eng.observe("cold_serve", t=50.0, value=9.0)  # bad blip
        assert eng.evaluate(51.0) == []  # long window burn ~ 2/51 < 2
        assert eng.state("cold_serve") == "ok"

    def test_fire_and_resolve_sequence(self):
        eng = _engine()
        # sustained badness: every observation bad -> burn 2.0 on all windows
        for t in range(20):
            eng.observe("cold_serve", t=float(t), value=5.0)
        fired = eng.evaluate(20.0)
        assert [a.action for a in fired] == ["fire"]
        assert eng.state("cold_serve") == "burning"
        assert eng.evaluate(21.0) == []  # already active: no re-fire
        # recovery: good observations push the SHORT window under threshold
        for t in range(22, 40):
            eng.observe("cold_serve", t=float(t), value=0.1)
        resolved = eng.evaluate(40.0)
        assert [a.action for a in resolved] == ["resolve"]
        assert eng.state("cold_serve") == "ok"
        assert [a.action for a in eng.alerts] == ["fire", "resolve"]

    def test_detection_delay_shrinks_with_regression_size(self):
        """Every injected bad fraction pages, and a larger one pages no later
        (multi-window burn alerting on the sim-scaled default rules; with
        objective 0.9 the faintest catchable fraction is just over 0.2)."""
        onset, delays = 600.0, []
        for frac in (0.25, 0.4, 0.6, 1.0):
            eng = SloEngine([SloSpec("cold_serve", objective=0.9, threshold=60.0,
                                     rules=default_burn_rules(1.0 / 60.0))])
            fired = None
            for i in range(7200):
                t = float(i)
                # observation i is bad with density frac after the onset
                bad = t >= onset and (i * 2654435761 % 1000) / 1000.0 < frac
                eng.observe("cold_serve", t=t, value=90.0 if bad else 1.0)
                if any(a.action == "fire" for a in eng.evaluate(t)):
                    fired = t - onset
                    break
            assert fired is not None and fired >= 0, frac
            delays.append(fired)
        assert delays == sorted(delays, reverse=True), delays

    def test_observe_counts_batches(self):
        eng = _engine()
        eng.observe_counts("cold_serve", t=0.0, good=6, bad=4)
        assert eng.burn_rate("cold_serve", 60.0, 1.0) == pytest.approx(0.8)
        with pytest.raises(ValueError):
            eng.observe_counts("cold_serve", t=2.0, good=-1)
        with pytest.raises(KeyError):
            eng.observe_counts("nope", t=2.0, good=1)

    def test_observations_must_be_time_ordered(self):
        eng = _engine()
        eng.observe("cold_serve", t=10.0, value=0.1)
        with pytest.raises(ValueError):
            eng.observe("cold_serve", t=9.0, value=0.1)

    def test_budget_remaining(self):
        eng = _engine(objective=0.5)
        assert eng.budget_remaining("cold_serve", 0.0) == 1.0  # no traffic
        for t in range(10):
            eng.observe("cold_serve", t=float(t), value=0.1)
        assert eng.budget_remaining("cold_serve", 10.0) == 1.0
        for t in range(10, 20):
            eng.observe("cold_serve", t=float(t), value=9.0)
        # 10 bad of 20 total, 10 allowed -> budget exactly exhausted
        assert eng.budget_remaining("cold_serve", 20.0) == pytest.approx(0.0)

    def test_ensure_is_idempotent_and_first_wins(self):
        eng = _engine()
        tmpl = eng.specs["cold_serve"]
        ct = eng.ensure(dataclasses.replace(tmpl, name="cold_serve_CT"))
        again = eng.ensure(dataclasses.replace(tmpl, name="cold_serve_CT",
                                               objective=0.9))
        assert again is ct and again.objective == tmpl.objective

    def test_replay_reproduces_alerts_bit_for_bit(self):
        eng = _engine()
        for t in range(30):
            eng.observe("cold_serve", t=float(t), value=5.0 if t < 20 else 0.1)
            if t % 5 == 0:
                eng.evaluate(float(t))
        eng.evaluate(30.0)
        assert eng.alerts  # scenario produced transitions
        fresh = eng.replay()
        assert fresh.alerts == eng.alerts
        assert fresh.digest() == eng.digest()

    def test_tampered_alerts_break_replay(self):
        eng = _engine()
        for t in range(10):
            eng.observe("cold_serve", t=float(t), value=5.0)
        eng.evaluate(10.0)
        eng.alerts.append(AlertEvent(11.0, "cold_serve", 0, "resolve",
                                     "page", 0.0, 0.0))
        assert eng.replay().alerts != eng.alerts

    def test_digest_stable_and_sensitive(self):
        def build(bad_from):
            eng = _engine()
            for t in range(20):
                eng.observe("cold_serve", t=float(t),
                            value=5.0 if t >= bad_from else 0.1)
            eng.evaluate(20.0)
            return eng.digest()

        assert build(0) == build(0)
        assert build(0) != build(20)  # alerts vs none

    def test_registry_counters(self):
        reg = MetricsRegistry()
        eng = SloEngine([SloSpec("cold_serve", objective=0.5, threshold=1.0,
                                 rules=FAST)], registry=reg)
        for t in range(10):
            eng.observe("cold_serve", t=float(t), value=5.0)
        eng.evaluate(10.0)
        snap = reg.snapshot()
        assert snap["repro_slo_observations"] == 10
        assert snap["repro_slo_alerts_fired"] == 1

    def test_default_burn_rules_scale(self):
        prod = default_burn_rules()
        sim = default_burn_rules(1.0 / 60.0)
        assert prod[0].long_window == 3600.0 and prod[0].short_window == 300.0
        assert sim[0].long_window == pytest.approx(60.0)
        assert sim[1].long_window == pytest.approx(4320.0)


# ------------------------------------------------------- histogram quantiles
class TestHistogramQuantiles:
    def test_empty_series_is_none(self):
        h = Histogram("repro_test_lat")
        assert h.quantile(0.5) is None
        with pytest.raises(ValueError):
            h.quantile(1.5)

    def test_single_value_is_exact(self):
        h = Histogram("repro_test_lat")
        for _ in range(7):
            h.observe(0.42)
        for q in (0.0, 0.5, 0.95, 0.99, 1.0):
            assert h.quantile(q) == pytest.approx(0.42)
        snap = h.snapshot()[""]
        assert snap["p50"] == snap["p99"] == pytest.approx(0.42)
        assert snap["min"] == snap["max"] == pytest.approx(0.42)

    def test_error_bounded_by_bucket_width(self):
        h = Histogram("repro_test_lat", buckets=(1.0, 2.0, 4.0, 8.0))
        values = [0.5, 1.5, 1.7, 3.0, 3.5, 5.0, 6.0, 7.0]
        for v in values:
            h.observe(v)
        svals = sorted(values)
        for q in (0.5, 0.95, 0.99):
            est = h.quantile(q)
            exact = svals[min(len(svals) - 1, int(q * len(svals)))]
            # containing bucket has width <= 4 here
            assert abs(est - exact) <= 4.0

    def test_snapshot_keys_and_labels(self):
        h = Histogram("repro_test_lat")
        h.observe(0.2, modality="CT")
        snap = h.snapshot()
        (key,) = snap.keys()
        assert key == '{modality="CT"}'
        assert set(snap[key]) == {"count", "sum", "min", "max", "p50", "p95", "p99"}

    def test_series_surface_unchanged(self):
        # min/max are internals: series() (and registry snapshots built on
        # it) still expose exactly counts/sum/count
        h = Histogram("repro_test_lat")
        h.observe(0.2)
        assert set(h.series()[""]) == {"counts", "sum", "count"}


# hypothesis property tests ride alongside, skipping cleanly without it
try:
    from hypothesis import HealthCheck, given, settings, strategies as st

    _settings = settings(max_examples=50, deadline=None,
                         suppress_health_check=[HealthCheck.too_slow])

    class TestQuantileProperties:
        @given(values=st.lists(
            st.floats(min_value=0.0, max_value=100.0,
                      allow_nan=False, allow_infinity=False),
            min_size=1, max_size=60,
        ))
        @_settings
        def test_monotone_and_bounded(self, values):
            h = Histogram("repro_test_lat")
            for v in values:
                h.observe(v)
            p50, p95, p99 = (h.quantile(q) for q in (0.50, 0.95, 0.99))
            assert p50 <= p95 <= p99
            assert min(values) <= p50 and p99 <= max(values)

        @given(
            value=st.floats(min_value=0.0, max_value=100.0,
                            allow_nan=False, allow_infinity=False),
            n=st.integers(min_value=1, max_value=20),
        )
        @_settings
        def test_single_bucket_exactness(self, value, n):
            h = Histogram("repro_test_lat")
            for _ in range(n):
                h.observe(value)
            for q in (0.5, 0.95, 0.99):
                assert h.quantile(q) == pytest.approx(value)
except ImportError:  # pragma: no cover
    pass


# --------------------------------------------------------------- the profiler
def _scripted_serve(tracer, key, publish_t, lease_t, busy_s, modality="CT",
                    retry_t=None):
    """Emit the broker/worker span chain of one cold serve onto the tracer's
    clock (a SimClock the caller advances)."""
    clock = tracer.clock
    attempt = 1
    clock.advance(publish_t - clock.now())
    tracer.event("broker.publish", trace_id=trace_id_for(key, 1), key=key, attempt=1)
    if retry_t is not None:
        attempt = 2
        clock.advance(retry_t - clock.now())
        tracer.event("broker.redeliver", trace_id=trace_id_for(key, 2), key=key, attempt=2)
    tid = trace_id_for(key, attempt)
    clock.advance(lease_t - clock.now())
    tracer.event("broker.lease", trace_id=tid, key=key)
    with tracer.span("worker.process", trace_id=tid, key=key) as proc:
        with tracer.span("worker.fetch", accession="A1") as f:
            f.set(nbytes=100, instances=1, modality=modality)
        with tracer.span("worker.deid", busy_s=busy_s):
            pass
        with tracer.span("worker.deliver", datasets=1):
            pass
        proc.set(ok=True, busy_s=busy_s)
    tracer.event("broker.ack", trace_id=tid, key=key)


class TestCriticalPathProfiler:
    def test_stage_attribution(self):
        clock = SimClock()
        tracer = Tracer(clock)
        # publish t=0, redeliver t=10, lease t=25, busy 3s -> retry 10,
        # queue 15, deid 3
        _scripted_serve(tracer, "IRB/A1", 0.0, 25.0, 3.0, retry_t=10.0)
        prof = CriticalPathProfiler()
        assert prof.fold(tracer.spans()) == 1
        cells = prof.profile()["cold"]["CT"]
        assert cells["retry"]["total_s"] == pytest.approx(10.0)
        assert cells["queue"]["total_s"] == pytest.approx(15.0)
        assert cells["deid"]["total_s"] == pytest.approx(3.0)

    def test_fold_counts_every_serve(self):
        tracer = Tracer(SimClock())
        for i in range(40):
            t = 10.0 * i
            _scripted_serve(tracer, f"IRB/A{i}", t, t + 2.0, 1.0)
        assert CriticalPathProfiler().fold(tracer.spans()) == 40

    def test_fold_is_idempotent(self):
        clock = SimClock()
        tracer = Tracer(clock)
        _scripted_serve(tracer, "IRB/A1", 0.0, 5.0, 1.0)
        prof = CriticalPathProfiler()
        prof.fold(tracer.spans())
        d1 = prof.digest()
        assert prof.fold(tracer.spans()) == 0  # same spans: no double count
        assert prof.digest() == d1

    def test_non_ok_acks_are_skipped(self):
        clock = SimClock()
        tracer = Tracer(clock)
        key = "IRB/A1"
        tid = trace_id_for(key, 1)
        tracer.event("broker.publish", trace_id=tid, key=key, attempt=1)
        tracer.event("broker.lease", trace_id=tid, key=key)
        with tracer.span("worker.process", trace_id=tid, key=key) as proc:
            proc.set(deduped=True)  # dedup ack, not a serve
        tracer.event("broker.ack", trace_id=tid, key=key)
        prof = CriticalPathProfiler()
        assert prof.fold(tracer.spans()) == 0

    def test_exports_are_phi_safe_and_deterministic(self):
        clock = SimClock()
        tracer = Tracer(clock)
        _scripted_serve(tracer, "IRB/A1", 0.0, 5.0, 1.0, modality="DOE^JOHN")
        prof = CriticalPathProfiler()
        prof.fold(tracer.spans())
        folded = prof.export_folded(Redactor())
        assert "DOE^JOHN" not in folded  # ^ is outside the safe charset
        assert "[redacted]" in folded
        chrome = prof.to_chrome_trace(Redactor())
        assert "DOE^JOHN" not in str(chrome)
        assert any(e.get("name", "").startswith("profile.")
                   for e in chrome["traceEvents"])

    def test_top_stages_ranked(self):
        clock = SimClock()
        tracer = Tracer(clock)
        _scripted_serve(tracer, "IRB/A1", 0.0, 40.0, 2.0)  # queue-dominated
        prof = CriticalPathProfiler()
        prof.fold(tracer.spans())
        stages = prof.top_stages(2)
        assert stages[0][0] == "queue"


# ------------------------------------------------------- health + closed loop
class TestHealthController:
    def _burning_engine(self):
        eng = _engine()
        for t in range(10):
            eng.observe("cold_serve", t=float(t), value=5.0)
        eng.evaluate(10.0)
        assert eng.state("cold_serve") == "burning"
        return eng

    def test_pressure_from_latency_alerts_only(self):
        eng = self._burning_engine()
        hc = HealthController(eng)
        assert hc.pressure() == pytest.approx(2.0)  # 1 + 1 per alert
        # a burning non-latency SLO adds no pressure
        eng2 = SloEngine([SloSpec("dlq_rate", objective=0.5, kind="rate",
                                  rules=FAST)])
        for t in range(10):
            eng2.observe("dlq_rate", t=float(t), good=False)
        eng2.evaluate(10.0)
        assert eng2.state("dlq_rate") == "burning"
        assert HealthController(eng2).pressure() == 1.0

    def test_pressure_capped(self):
        eng = self._burning_engine()
        hc = HealthController(eng, boost_per_alert=10.0, max_pressure=3.0)
        assert hc.pressure() == 3.0

    def test_snapshot_and_summary(self):
        eng = self._burning_engine()
        hc = HealthController(eng)
        rep = hc.snapshot(10.0)
        assert rep.states == {"cold_serve": "burning"}
        assert rep.burning == ["cold_serve"]
        assert rep.active_alerts == ["cold_serve#0"]
        assert rep.budget_remaining["cold_serve"] < 1.0
        assert "1/1 SLOs burning" in rep.summary()
        d = rep.to_dict()
        assert d["states"]["cold_serve"] == "burning"

    def test_service_health_report_requires_attachment(self, tmp_path):
        sim = _sim(tmp_path, "svc")
        sim.run()
        rep = sim.service.health_report()
        assert set(rep.states) >= {"warm_hit", "cohort_e2e", "dlq_rate"}
        sim2 = _sim(tmp_path, "svc2", slo=False)
        sim2.run()
        with pytest.raises(RuntimeError):
            sim2.service.health_report()


# ------------------------------------------------------------ fleet scenarios
def _sim(tmp_path, name, seed=9, n_studies=5, traffic=None, chaos=None, **cfg):
    config = FleetConfig(seed=seed, n_studies=n_studies, images_per_study=1,
                         **cfg)
    corpus = [f"SIM{i:04d}" for i in range(n_studies)]
    if traffic is None:
        traffic = BurstyTraffic(
            n_bursts=2, cohorts_per_burst=2, cohort_size=3
        ).schedule(corpus, seed=seed)
    if chaos is None:
        chaos = ChaosSchedule.seeded(seed, horizon=400.0, corpus=corpus)
    return FleetSim(config, traffic, tmp_path / f"{name}.jsonl", chaos)


def _straggler_sim(tmp_path, name, autoscale):
    """One big cohort + a straggler storm from t=0: latency burns, the
    backlog alone justifies few instances (generous window)."""
    n = 10
    corpus = [f"SIM{i:04d}" for i in range(n)]
    cfg = FleetConfig(
        seed=3, n_studies=n, images_per_study=2,
        delivery_window=3600.0, worker_throughput=2e6,
        max_instances=8, slo_cold_threshold=20.0, slo_autoscale=autoscale,
    )
    traffic = [CohortArrival(t=0.0, study_id="IRB-B", accessions=tuple(corpus))]
    chaos = ChaosSchedule([ChaosEvent(
        t=0.0, kind="set_straggler",
        payload={"rate": 1.0, "slow_factor": 20.0},
    )])
    return FleetSim(cfg, traffic, tmp_path / f"{name}.jsonl", chaos)


class TestSloFleetIntegration:
    def test_same_seed_bit_identical_alerts_and_profile(self, tmp_path):
        r1 = _sim(tmp_path, "a").run()
        r2 = _sim(tmp_path, "b").run()
        assert r1.ok() and r2.ok()
        assert r1.slo["alert_digest"] == r2.slo["alert_digest"]
        assert r1.slo["profile_digest"] == r2.slo["profile_digest"]
        assert r1.slo["alerts_fired"] >= 1  # chaos run actually alerts
        assert r1.log_digest == r2.log_digest

    def test_slo_off_is_zero_behavior_change(self, tmp_path):
        s_on = _sim(tmp_path, "on")
        r_on = s_on.run()
        s_off = _sim(tmp_path, "off", slo=False)
        r_off = s_off.run()
        assert r_off.ok()
        assert r_off.slo == {}
        # identical log (minus the additive slo_alert stream) and metrics
        assert s_on.log.digest(exclude_kinds=("slo_alert",)) == s_off.log.digest()
        assert r_on.metrics == r_off.metrics
        assert r_on.trace_digest == r_off.trace_digest

    def test_alert_log_records_match_engine(self, tmp_path):
        sim = _sim(tmp_path, "alerts")
        sim.run()
        logged = sim.log.by_kind("slo_alert")
        assert len(logged) == len(sim.slo_engine.alerts)
        for rec, alert in zip(logged, sim.slo_engine.alerts):
            assert rec["slo"] == alert.slo
            assert rec["action"] == alert.action

    def test_freshness_slo_registered_with_feed(self, tmp_path):
        sim = _sim(tmp_path, "feed", feed_mutations=6)
        r = sim.run()
        assert r.ok()
        assert "ingest_freshness" in r.slo["states"]
        assert any(rec["slo"] == "ingest_freshness"
                   for rec in sim.slo_engine.obs_log)

    def test_slo_conformance_negative_control_tampered_alerts(self, tmp_path):
        sim = _sim(tmp_path, "tamper")
        r = sim.run()
        assert r.ok()
        sim.slo_engine.alerts.append(AlertEvent(
            9999.0, "cold_serve_CT", 0, "fire", "page", 9.0, 9.0))
        out = SloConformance().check(sim)
        assert out and any("replay" in v.detail for v in out)

    def test_slo_conformance_negative_control_tampered_observations(self, tmp_path):
        sim = _sim(tmp_path, "tamper2")
        r = sim.run()
        assert r.ok()
        # forge a cold-serve observation the span stream never saw
        cold = [rec for rec in sim.slo_engine.obs_log
                if rec["slo"].startswith("cold_serve")]
        assert cold
        cold[-1]["value"] = 12345.0
        out = SloConformance().check(sim)
        assert any("span stream" in v.detail for v in out)

    def test_cold_serve_observations_equal_span_derivation(self, tmp_path):
        sim = _sim(tmp_path, "derive")
        sim.run()
        derived = derive_serve_observations(sim.tracer.spans())
        observed = [rec for rec in sim.slo_engine.obs_log
                    if rec["slo"].startswith("cold_serve")]
        assert len(derived) == len(observed) > 0
        for (t, _key, lat), rec in zip(derived, observed):
            assert rec["t"] == pytest.approx(t)
            assert rec["value"] == pytest.approx(lat)


class TestBurnAutoscaleLoop:
    def test_burn_signal_strictly_shortens_recovery(self, tmp_path):
        r_on = _straggler_sim(tmp_path, "on", autoscale=True).run()
        r_off = _straggler_sim(tmp_path, "off", autoscale=False).run()
        assert r_on.ok() and r_off.ok()
        assert r_on.slo["alerts_fired"] >= 1
        assert r_off.slo["alerts_fired"] >= 1  # engine alerts either way
        # the closed loop buys strictly faster drain AND lower worst latency
        assert r_on.metrics["sim_minutes"] < r_off.metrics["sim_minutes"]
        assert r_on.metrics["max_latency_s"] < r_off.metrics["max_latency_s"]

    def test_burn_scale_up_events_only_with_signal(self, tmp_path):
        s_on = _straggler_sim(tmp_path, "ev_on", autoscale=True)
        s_on.run()
        s_off = _straggler_sim(tmp_path, "ev_off", autoscale=False)
        s_off.run()
        on_reasons = {e.reason for e in s_on.pool.autoscaler.events}
        off_reasons = {e.reason for e in s_off.pool.autoscaler.events}
        assert "burn-scale-up" in on_reasons
        assert "burn-scale-up" not in off_reasons


# -------------------------------------------------- ExecutorStats migration
class TestExecutorStatsMigration:
    def test_attribute_surface_preserved(self):
        st = ExecutorStats()
        st.instances += 13
        st.dispatches += 2
        st.bucket_keys.add((512, 512, "uint16", 4))
        st.bucket_keys.add((512, 512, "uint16", 4))  # set semantics
        st.padded_shapes.add((8, 512, 512, "uint16", 4))
        assert st.instances == 13 and st.dispatches == 2
        assert st.buckets == 1
        assert (512, 512, "uint16", 4) in st.bucket_keys
        assert len(st.padded_shapes) == 1

    def test_registry_backed_counters_and_gauges(self):
        reg = MetricsRegistry()
        st = ExecutorStats(reg)
        st.instances += 5
        st.detect_dispatches += 1
        st.bucket_keys.update({(1,), (2,), (3,)})
        snap = reg.snapshot()
        assert snap["repro_executor_instances"] == 5
        assert snap["repro_executor_detect_dispatches"] == 1
        assert snap["repro_executor_bucket_keys"] == 3

    def test_shared_registry_aggregates_across_executors(self):
        reg = MetricsRegistry()
        a, b = ExecutorStats(reg), ExecutorStats(reg)
        a.instances += 3
        b.instances += 4
        assert reg.value("repro_executor_instances") == 7

    def test_metrics_conservation_executor_negative_control(self, tmp_path):
        sim = _sim(tmp_path, "exec")
        r = sim.run()
        assert r.ok()
        # tamper the executor-side ledger: the worker-side deltas no longer
        # balance and the conservation checker must fire
        sim.pipeline.executor.stats.instances += 1
        out = MetricsConservation().check(sim)
        assert any("executor batch accounting" in v.detail for v in out)
