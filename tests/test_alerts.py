"""Tests for the metric time-series store + SLO alert engine (ISSUE 16).

Covers the tentpole's contracts:

  * the memtrack-style gating identity for BOTH modules (dormant hooks ARE
    the module no-op references; shutdown restores the exact objects),
  * tiered downsampling (tier lengths, mean vs last bucket aggregation,
    endpoint-exact rates on cumulative series, window tier selection),
  * hand-computed multi-window multi-burn-rate fixtures,
  * the pending -> firing -> resolved lifecycle with ``for_s`` holds and
    firing dedup,
  * the FROZEN `/alerts` schema v1 (json round-trip, dormant shape),
  * rule packs + env-knob parsing.

Everything runs store/engine objects directly with explicit ``now``
timestamps — no sleeps, no wall-clock races.
"""

import json
import os
import subprocess
import sys
import warnings

import pytest

from vescale_tpu import telemetry
from vescale_tpu.telemetry import alerts as _alerts
from vescale_tpu.telemetry import timeseries as _ts
from vescale_tpu.telemetry.alerts import (
    ALERTS_FIELDS,
    ALERTS_RULE_FIELDS,
    ALERTS_SCHEMA_VERSION,
    AlertEngine,
    BurnRateRule,
    ManualRule,
    ThresholdRule,
    TrendRule,
    ZScoreRule,
    burn_windows_from_env,
    fleet_rule_pack,
    serve_rule_pack,
    train_rule_pack,
)
from vescale_tpu.telemetry.registry import MetricsRegistry
from vescale_tpu.telemetry.timeseries import Series, TimeSeriesStore

T0 = 1_000_000.0  # fixed epoch for explicit-now tests


# ------------------------------------------------------------------ helpers
def _store(cadence_s=0.0, base_len=512, tier_factor=8, tiers=3):
    return TimeSeriesStore(
        MetricsRegistry(),
        cadence_s=cadence_s,
        base_len=base_len,
        tier_factor=tier_factor,
        tiers=tiers,
    )


def _feed_gauge(store, metric, values, t0=T0, dt=1.0):
    """Set the gauge and force-sample once per value at t0, t0+dt, ..."""
    g = store.registry.gauge(metric)
    for i, v in enumerate(values):
        g.set(float(v))
        assert store.sample(now=t0 + i * dt, force=True)
    return t0 + (len(values) - 1) * dt


# ============================================================ gate identity
def test_timeseries_dormant_hook_is_noop_reference():
    assert not telemetry.is_active()
    assert _ts.sample is _ts._noop_sample
    assert _ts.get_store() is None and not _ts.is_active()
    assert _ts.sample("serve") is False  # callable, rejects, allocates nothing


def test_alerts_dormant_hooks_are_noop_references():
    assert not telemetry.is_active()
    assert _alerts.evaluate is _alerts._noop_evaluate
    assert _alerts.raise_alert is _alerts._fallback_raise_alert
    assert _alerts.resolve is _alerts._noop_resolve
    assert _alerts.get_engine() is None and not _alerts.is_active()
    assert _alerts.evaluate() == []
    assert _alerts.resolve("whatever") is None


def test_init_rebinds_and_shutdown_restores_exact_references():
    telemetry.init(out_dir=None, memtrack=False, timeseries=True, alerts=True)
    try:
        assert _ts.is_active() and _alerts.is_active()
        assert _ts.sample is not _ts._noop_sample
        assert _alerts.evaluate is not _alerts._noop_evaluate
        assert _alerts.raise_alert is not _alerts._fallback_raise_alert
        assert _alerts.resolve is not _alerts._noop_resolve
        # the engine evaluates over THE live store
        assert _alerts.get_engine().store is _ts.get_store()
    finally:
        telemetry.shutdown()
    # restoration is by identity, not equivalent-behavior (memtrack contract)
    assert _ts.sample is _ts._noop_sample
    assert _alerts.evaluate is _alerts._noop_evaluate
    assert _alerts.raise_alert is _alerts._fallback_raise_alert
    assert _alerts.resolve is _alerts._noop_resolve


def test_init_can_gate_each_module_off():
    telemetry.init(out_dir=None, memtrack=False, timeseries=False, alerts=False)
    try:
        assert not _ts.is_active() and not _alerts.is_active()
        assert _ts.sample is _ts._noop_sample
        assert _alerts.raise_alert is _alerts._fallback_raise_alert
    finally:
        telemetry.shutdown()


def test_dormant_raise_alert_warns_once_per_rule_name():
    _alerts.clear_fallback_warned()
    try:
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            _alerts.raise_alert("t-latch", message="first")
            _alerts.raise_alert("t-latch", message="second")  # latched
            _alerts.raise_alert("t-other", message="other rule still warns")
        msgs = [str(x.message) for x in w]
        assert len(msgs) == 2
        assert msgs[0] == "[alert:t-latch] first"
        assert msgs[1] == "[alert:t-other] other rule still warns"
        _alerts.clear_fallback_warned()
        with warnings.catch_warnings(record=True) as w2:
            warnings.simplefilter("always")
            _alerts.raise_alert("t-latch", message="after clear")
        assert len(w2) == 1
    finally:
        _alerts.clear_fallback_warned()


# ======================================================= tiered downsampling
def test_value_series_tier_buckets_are_means():
    s = Series("g", "value", base_len=512, tier_factor=4, tiers=3)
    # 16 samples -> tier1 gets 4 buckets of 4, tier2 gets 1 bucket of 4
    for i in range(16):
        s.append(T0 + i, float(i))
    assert len(s.tiers[0]) == 16
    assert len(s.tiers[1]) == 4
    assert len(s.tiers[2]) == 1
    # each tier-1 sample is the MEAN of its 4 raw values, stamped at the
    # bucket's last timestamp
    t1 = s.tiers[1].items()
    assert t1 == [
        (T0 + 3, 1.5),
        (T0 + 7, 5.5),
        (T0 + 11, 9.5),
        (T0 + 15, 13.5),
    ]
    # tier 2 aggregates tier-1 samples the same way
    assert s.tiers[2].items() == [(T0 + 15, (1.5 + 5.5 + 9.5 + 13.5) / 4)]


def test_cumulative_series_tier_buckets_keep_last_value():
    s = Series("c", "cumulative", base_len=512, tier_factor=4, tiers=2)
    for i in range(8):
        s.append(T0 + i, float(10 * (i + 1)))  # 10, 20, ..., 80
    # counter buckets keep the ENDPOINT, not the mean — rate math needs it
    assert s.tiers[1].items() == [(T0 + 3, 40.0), (T0 + 7, 80.0)]


def test_rate_is_endpoint_exact_through_downsampling():
    store = _store(base_len=8, tier_factor=4, tiers=3)
    c = store.registry.counter("ticks")
    for i in range(64):
        c.inc(5)  # +5 per second
        store.sample(now=T0 + i, force=True)
    # a span beyond tier 0's 8-sample reach answers from a coarse tier;
    # last-value bucket aggregation keeps delta/rate endpoint-exact
    rate = store.reduce("ticks", 40.0, "rate", now=T0 + 63)
    assert rate == pytest.approx(5.0, rel=1e-9)
    delta = store.reduce("ticks", 40.0, "delta", now=T0 + 63)
    assert delta == pytest.approx(delta, rel=1e-9) and delta % 5 == 0


def test_window_prefers_finest_covering_tier():
    s = Series("g", "value", base_len=8, tier_factor=4, tiers=3)
    for i in range(64):
        s.append(T0 + i, float(i))
    # tier0 retains the last 8 raw samples -> a 5 s span reads raw
    # (the cut is inclusive: now-5 .. now is 6 one-second samples)
    win = s.window(5.0, now=T0 + 63)
    assert [v for _, v in win] == [58.0, 59.0, 60.0, 61.0, 62.0, 63.0]
    # a 25 s span exceeds tier0's 8 s reach -> tier1 (4 s buckets,
    # earliest retained bucket T0+35 covers the T0+38 cut)
    win = s.window(25.0, now=T0 + 63)
    assert [t - T0 for t, _ in win] == [39.0, 43.0, 47.0, 51.0, 55.0, 59.0, 63.0]
    # a 30 s span exceeds tier1's 28 s reach too -> tier2 (16 s buckets)
    win = s.window(30.0, now=T0 + 63)
    assert [t - T0 for t, _ in win] == [47.0, 63.0]


def test_window_young_series_serves_all_samples():
    # regression: a single-sample series must answer ANY span from its
    # finest ring instead of an empty coarse tier
    s = Series("g", "value", base_len=8, tier_factor=4, tiers=3)
    s.append(T0, 0.5)
    assert s.window(60.0, now=T0 + 1.0) == [(T0, 0.5)]
    assert Series("e", "value", 8, 4, 2).window(60.0, now=T0) == []


def test_store_cadence_limits_global_sample_density():
    store = _store(cadence_s=1.0)
    store.registry.gauge("g").set(1.0)
    assert store.sample(now=T0)
    assert not store.sample(now=T0 + 0.25)  # within cadence: rejected
    assert not store.sample(now=T0 + 0.99)
    assert store.sample(now=T0 + 1.0)
    assert store.sample(now=T0 + 1.5, force=True)  # force bypasses
    assert store.samples_taken == 3


def test_histogram_expands_to_percentile_and_cumulative_series():
    store = _store()
    h = store.registry.histogram("lat")
    for v in (0.1, 0.2, 0.3):
        h.observe(v)
    store.sample(now=T0, force=True)
    names = store.names()
    for suffix in (":p50", ":p95", ":p99", ":count", ":sum"):
        assert f"lat{suffix}" in names
    assert store.reduce("lat:count", 60.0, "last", now=T0) == 3.0


# ===================================================== burn-rate fixtures
def test_burn_rate_fires_only_when_both_windows_exceed():
    store = _store()
    rule = BurnRateRule("burn", "m", slo=1.0, windows=((40.0, 10.0, 2.0),))
    # 40 s of metric == 3.0: long avg 3.0, short avg 3.0, slo 1.0
    # -> burn 3.0 on both windows, factor 2.0 -> fires
    now = _feed_gauge(store, "m", [3.0] * 41)
    hold, worst = rule.condition(store, now)
    assert hold and worst == pytest.approx(3.0)
    # recovery: 10 s of 0.0 drags the SHORT window under the factor while
    # the long window still burns -> must NOT hold (prompt reset)
    now = _feed_gauge(store, "m", [0.0] * 11, t0=now + 1.0)
    long_avg = store.reduce("m", 40.0, "avg", now=now)
    short_avg = store.reduce("m", 10.0, "avg", now=now)
    assert long_avg > 2.0 and short_avg < 2.0  # the fixture's premise
    hold, worst = rule.condition(store, now)
    assert not hold
    assert worst == pytest.approx(long_avg)  # worst burn still reported


def test_burn_rate_any_pair_suffices():
    store = _store()
    rule = BurnRateRule(
        "burn", "m", slo=2.0,
        windows=((100.0, 50.0, 100.0), (20.0, 5.0, 1.5)),
    )
    # avg 8.0 / slo 2.0 = burn 4.0: under the first pair's factor 100,
    # over the second pair's 1.5 -> holds via the second pair
    now = _feed_gauge(store, "m", [8.0] * 25)
    hold, worst = rule.condition(store, now)
    assert hold and worst == pytest.approx(4.0)


def test_burn_rate_needs_data_in_both_windows():
    store = _store()
    rule = BurnRateRule("burn", "m", slo=1.0, windows=((40.0, 10.0, 2.0),))
    hold, worst = rule.condition(store, T0)  # empty store
    assert not hold and worst is None


def test_burn_rate_validates_inputs():
    with pytest.raises(ValueError):
        BurnRateRule("b", "m", slo=0.0)
    with pytest.raises(ValueError):
        BurnRateRule("b", "m", slo=1.0, windows=())


# ==================================================== lifecycle + engine
def test_threshold_lifecycle_pending_firing_resolved():
    store = _store()
    eng = AlertEngine(store=store)
    eng.add_rule(ThresholdRule(
        "hot", "temp", ">", 100.0, window_s=30.0, reducer="last", for_s=10.0,
    ))
    g = store.registry.gauge("temp")

    g.set(50.0)
    store.sample(now=T0, force=True)
    assert eng.evaluate(now=T0) == []
    assert eng.state_of("hot")["state"] == "ok"

    # condition starts holding -> pending (for_s hold, not firing yet)
    g.set(150.0)
    store.sample(now=T0 + 1, force=True)
    (tr,) = eng.evaluate(now=T0 + 1)
    assert (tr["from"], tr["to"]) == ("ok", "pending")
    assert eng.pending() == ["hot"] and eng.firing() == []

    # still holding but inside the for_s window -> NO transition
    store.sample(now=T0 + 5, force=True)
    assert eng.evaluate(now=T0 + 5) == []
    assert eng.state_of("hot")["state"] == "pending"

    # held for >= for_s -> firing
    store.sample(now=T0 + 11, force=True)
    (tr,) = eng.evaluate(now=T0 + 11)
    assert (tr["from"], tr["to"]) == ("pending", "firing")
    assert eng.firing() == ["hot"]
    assert eng.state_of("hot")["fired_count"] == 1

    # holding while firing -> dedup: value refresh only, no transition
    g.set(200.0)
    store.sample(now=T0 + 12, force=True)
    assert eng.evaluate(now=T0 + 12) == []
    assert eng.state_of("hot")["value"] == 200.0

    # condition clears -> resolved (firing -> ok edge)
    g.set(50.0)
    store.sample(now=T0 + 20, force=True)
    (tr,) = eng.evaluate(now=T0 + 20)
    assert (tr["from"], tr["to"]) == ("firing", "ok")
    assert eng.firing() == [] and eng.counts["resolved"] == 1
    assert eng.counts["fired"] == 1


def test_pending_clears_without_firing_when_condition_drops():
    store = _store()
    eng = AlertEngine(store=store)
    eng.add_rule(ThresholdRule("hot", "temp", ">", 100.0, for_s=10.0,
                               window_s=30.0))
    g = store.registry.gauge("temp")
    g.set(150.0)
    store.sample(now=T0, force=True)
    eng.evaluate(now=T0)
    assert eng.state_of("hot")["state"] == "pending"
    g.set(50.0)
    store.sample(now=T0 + 2, force=True)
    (tr,) = eng.evaluate(now=T0 + 2)
    assert (tr["from"], tr["to"]) == ("pending", "ok")
    assert eng.counts["fired"] == 0  # a pending blip never counts as fired


def test_zero_for_s_fires_immediately():
    store = _store()
    eng = AlertEngine(store=store)
    eng.add_rule(ThresholdRule("hot", "temp", ">", 100.0, window_s=30.0))
    store.registry.gauge("temp").set(150.0)
    store.sample(now=T0, force=True)
    (tr,) = eng.evaluate(now=T0)
    assert (tr["from"], tr["to"]) == ("ok", "firing")


def test_resolve_for_s_holds_firing_through_quiet_blips():
    """ISSUE 19 satellite: the symmetric hysteresis on the way DOWN.  One
    quiet sample must not un-page; the rule has to stay below threshold
    for resolve_for_s before the firing -> ok edge."""
    store = _store()
    eng = AlertEngine(store=store)
    eng.add_rule(ThresholdRule(
        "hot", "temp", ">", 100.0, window_s=30.0, reducer="last",
        resolve_for_s=10.0,
    ))
    g = store.registry.gauge("temp")
    g.set(150.0)
    store.sample(now=T0, force=True)
    (tr,) = eng.evaluate(now=T0)
    assert (tr["from"], tr["to"]) == ("ok", "firing")

    # first quiet sample: below threshold, but inside the hold -> STILL
    # firing (value refreshes so the feed shows the current reading)
    g.set(50.0)
    store.sample(now=T0 + 5, force=True)
    assert eng.evaluate(now=T0 + 5) == []
    assert eng.firing() == ["hot"]
    assert eng.state_of("hot")["value"] == 50.0

    # flapping back above threshold RESETS the resolve clock
    g.set(150.0)
    store.sample(now=T0 + 8, force=True)
    assert eng.evaluate(now=T0 + 8) == []  # dedup: still firing
    g.set(50.0)
    store.sample(now=T0 + 12, force=True)
    assert eng.evaluate(now=T0 + 12) == []  # only 4s below since the flap

    # quiet long enough (12 -> 23 is > 10s below) -> resolve edge
    store.sample(now=T0 + 17, force=True)
    assert eng.evaluate(now=T0 + 17) == []
    store.sample(now=T0 + 23, force=True)
    (tr,) = eng.evaluate(now=T0 + 23)
    assert (tr["from"], tr["to"]) == ("firing", "ok")
    assert eng.counts["fired"] == 1 and eng.counts["resolved"] == 1


def test_resolve_for_s_zero_resolves_immediately_and_validates():
    store = _store()
    eng = AlertEngine(store=store)
    eng.add_rule(ThresholdRule("hot", "temp", ">", 100.0, window_s=30.0))
    g = store.registry.gauge("temp")
    g.set(150.0)
    store.sample(now=T0, force=True)
    eng.evaluate(now=T0)
    g.set(50.0)
    store.sample(now=T0 + 1, force=True)
    (tr,) = eng.evaluate(now=T0 + 1)  # default 0.0: old single-sample edge
    assert (tr["from"], tr["to"]) == ("firing", "ok")
    with pytest.raises(ValueError):
        ThresholdRule("bad", "temp", ">", 1.0, window_s=30.0,
                      resolve_for_s=-1.0)


def test_trend_rule_directions():
    store = _store()
    up = TrendRule("up", "q", slope_per_s=0.5, window_s=60.0, direction="up")
    down = TrendRule("dn", "q", slope_per_s=0.5, window_s=60.0,
                     direction="down")
    now = _feed_gauge(store, "q", [float(i) for i in range(10)])  # slope +1/s
    hold, slope = up.condition(store, now)
    assert hold and slope == pytest.approx(1.0)
    hold, _ = down.condition(store, now)
    assert not hold
    now2 = _feed_gauge(store, "q2", [float(-i) for i in range(10)])
    down2 = TrendRule("dn2", "q2", slope_per_s=0.5, window_s=60.0,
                      direction="down")
    hold, slope = down2.condition(store, now2)
    assert hold and slope == pytest.approx(-1.0)


def test_zscore_rule_excludes_latest_from_baseline():
    store = _store()
    rule = ZScoreRule("spike", "loss", z=4.0, window_s=600.0, min_samples=8,
                      direction="up")
    # 15 flat-ish samples then one huge spike; the spike must not dilute
    # its own baseline
    vals = [2.0, 2.1, 2.0, 1.9, 2.0, 2.1, 1.9, 2.0, 2.1, 2.0, 1.9, 2.0,
            2.1, 1.9, 2.0, 50.0]
    now = _feed_gauge(store, "loss", vals)
    hold, score = rule.condition(store, now)
    assert hold and score > 4.0
    # flat series (zero std) never divides by zero
    now2 = _feed_gauge(store, "flat", [3.0] * 16)
    flat = ZScoreRule("f", "flat", z=4.0, window_s=600.0, min_samples=8)
    assert flat.condition(store, now2) == (False, 0.0)


def test_manual_rule_raise_resolve_and_dedup():
    eng = AlertEngine(store=None)
    tr = eng.raise_alert("stall", message="watchdog stall", severity="critical",
                         value=12.0)
    assert (tr["from"], tr["to"]) == ("ok", "firing")
    st = eng.state_of("stall")
    assert st["state"] == "firing" and st["value"] == 12.0
    # dedup: re-raising refreshes value/message, returns no transition
    assert eng.raise_alert("stall", message="still stalled", value=13.0) is None
    st = eng.state_of("stall")
    assert st["value"] == 13.0 and st["message"] == "still stalled"
    assert st["fired_count"] == 1
    tr = eng.resolve("stall")
    assert (tr["from"], tr["to"]) == ("firing", "ok")
    assert eng.resolve("stall") is None  # already ok
    assert eng.resolve("never-existed") is None


def test_raise_alert_rejects_declarative_rules():
    store = _store()
    eng = AlertEngine(store=store)
    eng.add_rule(ThresholdRule("hot", "temp", ">", 1.0))
    with pytest.raises(TypeError):
        eng.raise_alert("hot", message="nope")


def test_manual_rule_survives_evaluate():
    # evaluate() must not resolve a raised manual alert (its condition IS
    # the raised flag) and must resolve it after resolve()
    eng = AlertEngine(store=None)
    eng.raise_alert("stall", message="x")
    assert eng.evaluate(now=T0) == []
    assert eng.firing() == ["stall"]
    eng.resolve("stall")
    assert eng.evaluate(now=T0 + 1) == []
    assert eng.firing() == []


def test_arm_pack_is_idempotent():
    eng = AlertEngine(store=_store())
    assert eng.arm_pack("serve", serve_rule_pack()) is True
    n = len(eng.rules)
    assert eng.arm_pack("serve", serve_rule_pack()) is False  # already armed
    assert len(eng.rules) == n
    assert eng.arm_pack("train", train_rule_pack()) is True
    assert len(eng.rules) > n


def test_broken_rule_does_not_kill_evaluation():
    store = _store()
    eng = AlertEngine(store=store)

    class _Boom(ThresholdRule):
        def condition(self, s, now):
            raise RuntimeError("boom")

    eng.add_rule(_Boom("boom", "m", ">", 0.0))
    eng.add_rule(ThresholdRule("ok-rule", "temp", ">", 100.0, window_s=30.0))
    store.registry.gauge("temp").set(150.0)
    store.sample(now=T0, force=True)
    (tr,) = eng.evaluate(now=T0)
    assert tr["rule"] == "ok-rule"
    assert eng.state_of("boom")["state"] == "ok"


def test_min_eval_interval_rate_limits():
    store = _store()
    eng = AlertEngine(store=store, min_eval_interval_s=5.0)
    eng.add_rule(ThresholdRule("hot", "temp", ">", 100.0, window_s=30.0))
    store.registry.gauge("temp").set(150.0)
    store.sample(now=T0, force=True)
    assert len(eng.evaluate(now=T0)) == 1
    assert eng.evaluate(now=T0 + 1) == []  # rate-limited, not state-driven
    assert eng.counts["evaluations"] == 1


def test_history_ring_is_bounded():
    eng = AlertEngine(store=None, history=8)
    for i in range(20):
        eng.raise_alert(f"r{i}", message="m")
    assert len(eng.history) == 8
    assert eng.history[-1]["rule"] == "r19"


# ==================================================== frozen /alerts schema
def test_payload_dormant_round_trips_frozen_schema():
    assert not _alerts.is_active()
    out = json.loads(json.dumps(_alerts.payload()))
    assert set(out) == ALERTS_FIELDS
    assert out["schema_version"] == ALERTS_SCHEMA_VERSION == 1
    assert out["active"] is False
    assert out["rules"] == {} and out["firing"] == [] and out["pending"] == []
    assert set(out["counts"]) == {"fired", "resolved", "pending", "evaluations"}


def test_payload_live_round_trips_frozen_schema():
    telemetry.init(out_dir=None, memtrack=False, timeseries=True, alerts=True)
    try:
        eng = _alerts.get_engine()
        store = _ts.get_store()
        eng.arm_pack("serve", serve_rule_pack(slo_ttft_s=0.5))
        eng.raise_alert("manual-probe", message="raised by test", value=1.0)
        store.registry.gauge("serve_shed_rate").set(0.9)
        store.sample(force=True)
        _alerts.evaluate()
        out = json.loads(json.dumps(_alerts.payload()))
        assert set(out) == ALERTS_FIELDS
        assert out["active"] is True
        assert "manual-probe" in out["firing"]
        assert "serve-shed-rate" in out["firing"]
        for name, row in out["rules"].items():
            assert set(row) == ALERTS_RULE_FIELDS, name
        assert out["counts"]["fired"] >= 2
        kinds = {r["kind"] for r in out["rules"].values()}
        assert {"threshold", "trend", "burn_rate", "manual"} <= kinds
        # history entries are json-native too
        assert out["history"][-1]["to"] == "firing"
    finally:
        telemetry.shutdown()


def test_digest_shape_dormant_and_live():
    assert _alerts.digest() == {"active": False, "firing": [], "pending": []}
    telemetry.init(out_dir=None, memtrack=False, timeseries=True, alerts=True)
    try:
        _alerts.raise_alert("d1", message="x")
        d = json.loads(json.dumps(_alerts.digest()))
        assert d == {"active": True, "firing": ["d1"], "pending": []}
    finally:
        telemetry.shutdown()


def test_transitions_feed_registry_counters_and_state_gauges():
    telemetry.init(out_dir=None, memtrack=False, timeseries=True, alerts=True)
    try:
        reg = telemetry.get_registry()
        _alerts.raise_alert("probe", message="x")
        assert reg.counter("alerts_fired_total").value == 1
        # prom-exportable per-rule state gauge: 2 == firing
        assert reg.gauge("alerts_state_probe").value == 2.0
        assert reg.gauge("alerts_firing").value == 1.0
        _alerts.resolve("probe")
        assert reg.counter("alerts_resolved_total").value == 1
        assert reg.gauge("alerts_state_probe").value == 0.0
    finally:
        telemetry.shutdown()


# ======================================================== packs + env knobs
def test_serve_pack_burn_rule_needs_slo():
    names = {r.name for r in serve_rule_pack()}
    assert "serve-ttft-slo-burn" not in names
    names = {r.name for r in serve_rule_pack(slo_ttft_s=0.5)}
    assert "serve-ttft-slo-burn" in names


def test_fleet_pack_burn_rule_needs_slo():
    names = {r.name for r in fleet_rule_pack()}
    assert "fleet-ttft-slo-burn" not in names
    rules = {r.name: r for r in fleet_rule_pack(slo_ttft_s=0.25)}
    assert rules["fleet-ttft-slo-burn"].slo == 0.25


def test_burn_windows_env_parsing(monkeypatch):
    monkeypatch.delenv("VESCALE_ALERTS_BURN_WINDOWS", raising=False)
    assert burn_windows_from_env() is None
    monkeypatch.setenv("VESCALE_ALERTS_BURN_WINDOWS", "3600:300:14.4,60:5:2")
    assert burn_windows_from_env() == ((3600.0, 300.0, 14.4), (60.0, 5.0, 2.0))
    monkeypatch.setenv("VESCALE_ALERTS_BURN_WINDOWS", "3600:300")
    with pytest.raises(ValueError):
        burn_windows_from_env()


def test_serve_pack_burn_knobs_from_env(monkeypatch):
    monkeypatch.setenv("VESCALE_ALERTS_BURN_WINDOWS", "120:10:3")
    monkeypatch.setenv("VESCALE_ALERTS_BURN_FOR_S", "7.5")
    (burn,) = [r for r in serve_rule_pack(slo_ttft_s=0.5)
               if r.name == "serve-ttft-slo-burn"]
    assert burn.windows == ((120.0, 10.0, 3.0),)
    assert burn.for_s == 7.5
    # explicit args beat the env
    (burn,) = [r for r in serve_rule_pack(
        slo_ttft_s=0.5, burn_windows=((60.0, 5.0, 2.0),), burn_for_s=0.0)
        if r.name == "serve-ttft-slo-burn"]
    assert burn.windows == ((60.0, 5.0, 2.0),) and burn.for_s == 0.0


def test_rule_validation():
    with pytest.raises(ValueError):
        ThresholdRule("x", "m", "!=", 1.0)
    with pytest.raises(ValueError):
        ThresholdRule("x", "m", ">", 1.0, severity="fatal")
    with pytest.raises(ValueError):
        TrendRule("x", "m", slope_per_s=-1.0)
    with pytest.raises(ValueError):
        TrendRule("x", "m", slope_per_s=1.0, direction="sideways")
    with pytest.raises(ValueError):
        ZScoreRule("x", "m", direction="diagonal")
    with pytest.raises(ValueError):
        Rule = ThresholdRule
        Rule("x", "m", ">", 1.0, for_s=-1.0)


# ============================================================ smoke wiring
def test_alert_smoke_script():
    """tier-1 wiring of scripts/alert_smoke.py: the 2-proc run where an
    injected slow_decode fault drives the multi-window burn-rate rule
    pending->firing->resolved on the live /alerts endpoint, with the
    firing visible in the /router v4 digest, the prom export and as an
    ALERT span on the merged fleet timeline."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, os.path.join(repo, "scripts", "alert_smoke.py")],
        capture_output=True,
        text=True,
        timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode == 0, (
        f"stdout:\n{out.stdout[-3000:]}\nstderr:\n{out.stderr[-3000:]}"
    )
    assert "ALERT SMOKE PASS" in out.stdout


def test_record_step_drives_sampling_and_evaluation():
    """The integration seam: one record_step() samples the store AND
    advances lifecycles — no separate pump needed by the loops."""
    telemetry.init(out_dir=None, memtrack=False, timeseries=True, alerts=True,
                   timeseries_cadence_s=0.0)
    try:
        eng = _alerts.get_engine()
        eng.add_rule(ThresholdRule("loss-high", "train_loss", ">", 10.0,
                                   window_s=60.0))
        telemetry.record_step({"loss": 50.0})
        assert eng.firing() == ["loss-high"]
        assert _ts.get_store().samples_taken >= 1
    finally:
        telemetry.shutdown()
