"""Training-health sentinel (system/sentinel.py,
docs/observability.md §Alerting).

Fake clocks everywhere: the rule state machine (pending → firing →
resolved), `for:` hold windows, cooldowns, absence-of-signal grace, and
rolling baselines are all driven by injected monotonic/wall clocks —
zero real sleeps. Evidence/inhibit/pause side effects are injected fns
except where the test is specifically about the real wiring
(name-resolve silence + inhibit keys, the aggregator hosting the
engine).
"""

import json
import os
import threading

import pytest

from areal_tpu.api.train_config import SentinelConfig, TelemetryConfig
from areal_tpu.base import name_resolve, names, telemetry
from areal_tpu.system import sentinel as sn
from areal_tpu.system.sentinel import (
    DEFAULT_RULES,
    Sentinel,
    SentinelConfigError,
    parse_duration,
    parse_rules,
    rules_from_config,
)

pytestmark = pytest.mark.sentinel


def make_sentinel(tmp_path, rules, *, cfg=None, stitcher=None,
                  flight=None, inhibit=None, pause=None):
    """A fully fake-clocked sentinel; returns (sentinel, clock_setter,
    wall_setter, captured side effects)."""
    t = {"mono": 0.0, "wall": 1_000.0}
    captured = {"flight": [], "inhibit": [], "pause": 0}

    def _pause():
        captured["pause"] += 1

    s = Sentinel(
        cfg or SentinelConfig(enabled=True, eval_interval_secs=0.1),
        "sentexp", "t0",
        rules=rules,
        stitcher=stitcher,
        alerts_path=str(tmp_path / "alerts.jsonl"),
        evidence_dir=str(tmp_path / "evidence"),
        clock=lambda: t["mono"],
        wall=lambda: t["wall"],
        flight_fn=flight or captured["flight"].append,
        inhibit_fn=inhibit or captured["inhibit"].append,
        pause_fn=pause or _pause,
    )

    def at(mono, wall=None):
        t["mono"] = mono
        if wall is not None:
            t["wall"] = wall

    return s, at, captured


def read_alerts(tmp_path):
    p = tmp_path / "alerts.jsonl"
    if not p.exists():
        return []
    with open(p) as f:
        return [json.loads(ln) for ln in f if ln.strip()]


THRESH = {"id": "kl", "metric": "train/approx_kl", "kind": "threshold",
          "op": "gt", "value": 1.0, "for": 2, "cooldown": 30,
          "severity": "critical"}


# ---------------------------------------------------------------------------
# rule grammar / parse-time validation
# ---------------------------------------------------------------------------


def test_parse_duration_units():
    assert parse_duration(30) == 30.0
    assert parse_duration("30") == 30.0
    assert parse_duration("500ms") == 0.5
    assert parse_duration("5m") == 300.0
    assert parse_duration("1.5h") == 5400.0
    with pytest.raises(ValueError):
        parse_duration("soon")


def test_default_rule_pack_parses():
    rules = rules_from_config(SentinelConfig(enabled=True))
    ids = [r.id for r in rules]
    assert len(ids) == len(set(ids)) == len(DEFAULT_RULES)
    assert all(r.severity in sn.SEVERITIES for r in rules)
    assert all(r.metric in sn.METRIC_CATALOG for r in rules)
    # and the pack can be dropped entirely
    assert rules_from_config(
        SentinelConfig(enabled=True, default_rules=False)
    ) == []


def test_parse_rejects_unknown_metric_naming_the_rule():
    with pytest.raises(SentinelConfigError, match="'kl'"):
        parse_rules([dict(THRESH, metric="train/approx_klx")])


def test_parse_rejects_nonpositive_durations():
    with pytest.raises(SentinelConfigError, match="'for'"):
        parse_rules([dict(THRESH, **{"for": 0})])
    with pytest.raises(SentinelConfigError, match="cooldown"):
        parse_rules([dict(THRESH, cooldown=-5)])
    with pytest.raises(SentinelConfigError, match="window"):
        parse_rules([dict(THRESH, window=0)])


def test_parse_rejects_duplicates_and_bad_enums():
    with pytest.raises(SentinelConfigError, match="duplicate"):
        parse_rules([THRESH, dict(THRESH, severity="warn")])
    for field, bad in (("kind", "slope"), ("severity", "fatal"),
                       ("op", "=="), ("agg", "p99"), ("action", "nuke")):
        with pytest.raises(SentinelConfigError, match=field):
            parse_rules([dict(THRESH, **{field: bad})])
    with pytest.raises(SentinelConfigError, match="id"):
        parse_rules([{"metric": "train/approx_kl"}])


def test_validate_config_front_runs_the_rule_pack():
    from areal_tpu.api import cli_args
    from areal_tpu.experiments.ppo_math_exp import PPOMATHConfig

    cfg = PPOMATHConfig()
    cfg.sentinel.enabled = True
    # the sentinel lives in the master's aggregator: telemetry required
    with pytest.raises(cli_args.ConfigError, match="telemetry"):
        cli_args.validate_config(cfg)
    cfg.telemetry.enabled = True
    cli_args.validate_config(cfg)  # default pack is valid
    cfg.sentinel.rules = [{"id": "bad", "metric": "no/such_metric"}]
    with pytest.raises(cli_args.ConfigError, match="'bad'"):
        cli_args.validate_config(cfg)
    # duplicate against the default pack is caught too
    cfg.sentinel.rules = [dict(DEFAULT_RULES[0])]
    with pytest.raises(cli_args.ConfigError, match="duplicate"):
        cli_args.validate_config(cfg)


# ---------------------------------------------------------------------------
# state machine
# ---------------------------------------------------------------------------


def test_threshold_pending_firing_resolved(tmp_path):
    s, at, cap = make_sentinel(tmp_path, parse_rules([THRESH]))
    s.feed("trainer", {"train/approx_kl{mfc=actor_train}": 0.2}, now=0.0)
    s.tick(0.0)
    assert s.states()["kl"]["state"] == "ok"
    at(1.0)
    s.feed("trainer", {"train/approx_kl{mfc=actor_train}": 2.0}, now=1.0)
    s.tick(1.0)
    # predicate holds but the `for:` window has not elapsed yet
    assert s.states()["kl"]["state"] == "pending"
    assert read_alerts(tmp_path) == []
    at(3.5, 1010.0)
    s.tick(3.5)
    assert s.states()["kl"]["state"] == "firing"
    recs = read_alerts(tmp_path)
    assert [r["event"] for r in recs] == ["firing"]
    assert recs[0]["rule"] == "kl" and recs[0]["severity"] == "critical"
    assert recs[0]["value"] == 2.0
    snap = s.registry.snapshot()
    assert snap["counters"]["alerts{rule=kl,severity=critical}"] == 1.0
    assert snap["gauges"]["alert_active{rule=kl}"] == 1.0
    # one evidence bundle + the critical autoscale-inhibit hint
    assert len(cap["flight"]) == 1 and len(cap["inhibit"]) == 1
    # recovery resolves the alert
    at(5.0)
    s.feed("trainer", {"train/approx_kl{mfc=actor_train}": 0.1}, now=5.0)
    s.tick(5.0)
    assert s.states()["kl"]["state"] == "ok"
    assert read_alerts(tmp_path)[-1]["event"] == "resolved"
    assert s.registry.snapshot()["gauges"]["alert_active{rule=kl}"] == 0.0


def test_blip_shorter_than_for_window_never_fires(tmp_path):
    s, at, cap = make_sentinel(tmp_path, parse_rules([THRESH]))
    at(1.0)
    s.feed("trainer", {"train/approx_kl": 5.0}, now=1.0)
    s.tick(1.0)
    at(2.0)
    s.feed("trainer", {"train/approx_kl": 0.1}, now=2.0)  # blip over
    s.tick(2.0)
    at(10.0)
    s.tick(10.0)
    assert s.states()["kl"]["state"] == "ok"
    assert read_alerts(tmp_path) == [] and cap["flight"] == []


def test_cooldown_bounds_refires(tmp_path):
    s, at, cap = make_sentinel(tmp_path, parse_rules([THRESH]))

    def trip(t0):
        at(t0)
        s.feed("trainer", {"train/approx_kl": 3.0}, now=t0)
        s.tick(t0)
        at(t0 + 2.5)
        s.tick(t0 + 2.5)

    def clear(t0):
        at(t0)
        s.feed("trainer", {"train/approx_kl": 0.0}, now=t0)
        s.tick(t0)

    trip(0.0)
    assert s.states()["kl"]["fires"] == 1
    clear(5.0)
    # re-trip inside the 30s cooldown: held pending, no second fire
    trip(10.0)
    assert s.states()["kl"]["state"] == "pending"
    assert s.states()["kl"]["fires"] == 1
    # past the cooldown it fires again
    at(40.0)
    s.tick(40.0)
    assert s.states()["kl"]["state"] == "firing"
    assert s.states()["kl"]["fires"] == 2


def test_absence_of_signal(tmp_path):
    rules = parse_rules([
        {"id": "stalled", "metric": "train/optimizer_steps",
         "kind": "absence", "for": 60, "cooldown": 60,
         "severity": "critical"},
    ])
    s, at, cap = make_sentinel(tmp_path, rules)
    # never-seen metric gets the startup grace: quiet until `for` elapses
    at(30.0)
    s.tick(30.0)
    assert s.states()["stalled"]["state"] == "ok"
    at(61.0)
    s.tick(61.0)
    assert s.states()["stalled"]["state"] == "firing"
    # a sample arriving resolves it
    at(70.0)
    s.feed("trainer", {"train/optimizer_steps": 12.0}, now=70.0)
    s.tick(70.0)
    assert s.states()["stalled"]["state"] == "ok"
    events = [r["event"] for r in read_alerts(tmp_path)]
    assert events == ["firing", "resolved"]


def test_absence_detects_wedged_but_flushing_producer(tmp_path):
    """Workers flush their full cumulative registry every interval, so a
    wedged trainer keeps DELIVERING train/optimizer_steps — absence must
    key off the value changing, not mere sample arrival."""
    rules = parse_rules([
        {"id": "stalled", "metric": "train/optimizer_steps",
         "kind": "absence", "for": 60, "cooldown": 60,
         "severity": "critical"},
    ])
    s, at, cap = make_sentinel(tmp_path, rules)
    for t in (0.0, 30.0, 59.0):  # healthy: the counter advances
        at(t)
        s.feed("trainer", {}, {"train/optimizer_steps": t + 1}, now=t)
        s.tick(t)
    assert s.states()["stalled"]["state"] == "ok"
    # wedged: snapshots keep arriving but the value never moves
    for t in (70.0, 90.0, 110.0, 125.0):
        at(t)
        s.feed("trainer", {}, {"train/optimizer_steps": 60.0}, now=t)
        s.tick(t)
    assert s.states()["stalled"]["state"] == "firing"
    # the next real optimizer step resolves it
    at(130.0)
    s.feed("trainer", {}, {"train/optimizer_steps": 61.0}, now=130.0)
    s.tick(130.0)
    assert s.states()["stalled"]["state"] == "ok"


def test_departed_worker_sources_expire(tmp_path):
    """A scaled-down/evicted worker's last reading must not pin a
    max-aggregate (and a false alert) forever."""
    rules = parse_rules([
        {"id": "worst", "metric": "rollout/staleness_current",
         "op": "gt", "value": 7.0, "for": 1, "cooldown": 10,
         "agg": "max", "severity": "warn"},
    ])
    cfg = SentinelConfig(enabled=True, eval_interval_secs=0.1,
                         source_expiry_secs=30.0)
    s, at, cap = make_sentinel(tmp_path, rules, cfg=cfg)
    s.feed("rollout:0", {"rollout/staleness_current": 1.0}, now=0.0)
    s.feed("rollout:1", {"rollout/staleness_current": 9.0}, now=0.0)
    s.tick(0.0)
    at(2.0)
    s.tick(2.0)
    assert s.states()["worst"]["state"] == "firing"
    # rollout:1 departs; rollout:0 keeps reporting a healthy value
    for t in (10.0, 20.0, 31.0):
        at(t)
        s.feed("rollout:0", {"rollout/staleness_current": 1.0}, now=t)
        s.tick(t)
    st = s.states()["worst"]
    assert st["state"] == "ok" and st["value"] == 1.0


def test_silence_is_cached_not_polled(tmp_path, tmp_name_resolve,
                                      monkeypatch):
    """An active alert under a long silence must not hit name-resolve
    every tick: the expiry is cached after the first suppressed fire."""
    reads = {"n": 0}
    real_get = name_resolve.get

    def counting_get(key):
        if "sentinel_silence" in key:
            reads["n"] += 1
        return real_get(key)

    monkeypatch.setattr(name_resolve, "get", counting_get)
    name_resolve.add(
        names.sentinel_silence("sentexp", "t0", "kl"),
        json.dumps({"until": 5_000.0}), replace=True,
    )
    s, at, cap = make_sentinel(tmp_path, parse_rules([THRESH]))
    at(0.0)
    s.feed("trainer", {"train/approx_kl": 9.0}, now=0.0)
    for t in range(1, 40):
        at(float(t))
        s.tick(float(t))
    assert s.states()["kl"]["state"] == "pending"
    assert reads["n"] == 1  # one real read; the rest served from cache
    assert s.registry.snapshot()["counters"][
        "sentinel/silenced{rule=kl}"] == 1.0


def test_rate_rule_differentiates_counters(tmp_path):
    rules = parse_rules([
        {"id": "failover_storm", "metric": "rollout/failovers",
         "kind": "rate", "op": "gt", "value": 1.0, "for": 1,
         "window": 30, "cooldown": 60, "severity": "warn"},
    ])
    s, at, cap = make_sentinel(tmp_path, rules)
    # slope 0.5/s: below the 1/s threshold
    for i, v in enumerate([0, 5, 10]):
        at(float(i * 10))
        s.feed("rollout", {}, {"rollout/failovers": float(v)},
               now=float(i * 10))
        s.tick(float(i * 10))
    assert s.states()["failover_storm"]["state"] == "ok"
    # slope jumps to 5/s
    at(31.0)
    s.feed("rollout", {}, {"rollout/failovers": 115.0}, now=31.0)
    s.tick(31.0)
    at(33.0)
    s.tick(33.0)
    assert s.states()["failover_storm"]["state"] == "firing"


def test_baseline_deviation(tmp_path):
    rules = parse_rules([
        {"id": "grad_spike", "metric": "train/grad_norm",
         "kind": "baseline", "value": 6.0, "for": 1, "window": 300,
         "cooldown": 60, "severity": "warn"},
    ])
    s, at, cap = make_sentinel(tmp_path, rules)
    # a stable baseline with mild jitter — never fires, even early when
    # there are too few points to judge
    vals = [1.0, 1.1, 0.9, 1.0, 1.05, 0.95, 1.0, 1.1, 0.9, 1.0]
    for i, v in enumerate(vals):
        at(float(i))
        s.feed("trainer", {"train/grad_norm": v}, now=float(i))
        s.tick(float(i))
    assert s.states()["grad_spike"]["state"] == "ok"
    # a 50x outlier is far beyond 6 deviations
    at(11.0)
    s.feed("trainer", {"train/grad_norm": 50.0}, now=11.0)
    s.tick(11.0)
    at(12.5)
    s.tick(12.5)
    assert s.states()["grad_spike"]["state"] == "firing"


def test_goodput_collapse_default_rule(tmp_path):
    """The default-pack goodput_collapse rule (docs/observability.md
    §Goodput) fed the aggregator-derived fleet/goodput series (source
    "fleet:0", exactly how TelemetryAggregator._ingest feeds it): a
    stable busy fleet stays quiet; chips going idle fires warn after the
    rule's for: hold — and the rolling-median baseline survives the
    anomaly's own points (it must not self-clear)."""
    raw = next(r for r in sn.DEFAULT_RULES if r["id"] == "goodput_collapse")
    rules = parse_rules([dict(raw)])
    s, at, cap = make_sentinel(tmp_path, rules)
    # 300s of healthy fleet goodput around 0.8 with mild jitter
    for i in range(300):
        at(float(i))
        s.feed("fleet:0", {"fleet/goodput": 0.8 + 0.01 * (i % 3)},
               now=float(i))
        s.tick(float(i))
    assert s.states()["goodput_collapse"]["state"] == "ok"
    # collapse: the fleet goes near-idle and STAYS there through the
    # 60s for: hold (the 1200s median baseline is still dominated by
    # the healthy history, so the anomaly cannot poison it)
    for i in range(300, 380):
        at(float(i))
        s.feed("fleet:0", {"fleet/goodput": 0.05}, now=float(i))
        s.tick(float(i))
    assert s.states()["goodput_collapse"]["state"] == "firing"
    firing = [r for r in read_alerts(tmp_path)
              if r["event"] == "firing"]
    assert firing and firing[0]["rule"] == "goodput_collapse"
    assert firing[0]["severity"] == "warn"
    assert firing[0]["value"] == 0.05


def test_agg_across_workers_and_label_values(tmp_path):
    rules = parse_rules([
        {"id": "worst", "metric": "rollout/staleness_current",
         "op": "gt", "value": 7.0, "for": 1, "cooldown": 60,
         "agg": "max", "severity": "warn"},
        {"id": "typical", "metric": "rollout/staleness_current",
         "op": "gt", "value": 7.0, "for": 1, "cooldown": 60,
         "agg": "mean", "severity": "warn"},
    ])
    s, at, cap = make_sentinel(tmp_path, rules)
    s.feed("rollout", {"rollout/staleness_current": 1.0}, now=0.0)
    # a second source: same worker kind, different index/labels
    s.feed("rollout2", {"rollout/staleness_current": 9.0}, now=0.0)
    s.tick(0.0)
    at(1.5)
    s.tick(1.5)
    st = s.states()
    # max over sources trips; the mean (5.0) stays under threshold
    assert st["worst"]["state"] == "firing"
    assert st["typical"]["state"] == "ok"
    assert st["worst"]["value"] == 9.0 and st["typical"]["value"] == 5.0


# ---------------------------------------------------------------------------
# silences, evidence, inhibit, pause
# ---------------------------------------------------------------------------


def test_silence_suppresses_fire_until_expiry(tmp_path, tmp_name_resolve):
    s, at, cap = make_sentinel(tmp_path, parse_rules([THRESH]))
    name_resolve.add(
        names.sentinel_silence("sentexp", "t0", "kl"),
        json.dumps({"until": 1_500.0}), replace=True,
    )
    at(0.0)
    s.feed("trainer", {"train/approx_kl": 5.0}, now=0.0)
    s.tick(0.0)
    at(3.0)  # wall stays 1000 < 1500: silenced
    s.tick(3.0)
    assert s.states()["kl"]["state"] == "pending"
    assert read_alerts(tmp_path) == [] and cap["flight"] == []
    assert s.registry.snapshot()["counters"][
        "sentinel/silenced{rule=kl}"] >= 1.0
    # silence expires (wall moves past `until`): the held alert fires
    at(4.0, 2_000.0)
    s.tick(4.0)
    assert s.states()["kl"]["state"] == "firing"


def test_evidence_bundle_layout_and_cap(tmp_path):
    class FakeStitcher:
        def recent_trace_ids(self, n):
            return ["trace-a", "trace-b"][:n]

    cfg = SentinelConfig(enabled=True, eval_interval_secs=0.1,
                         max_evidence_bundles=1)
    s, at, cap = make_sentinel(
        tmp_path, parse_rules([THRESH]), cfg=cfg, stitcher=FakeStitcher()
    )
    at(0.0)
    s.feed("trainer", {"train/approx_kl{mfc=actor_train}": 3.0}, now=0.0)
    s.tick(0.0)
    at(2.5)
    s.tick(2.5)
    bundles = os.listdir(tmp_path / "evidence")
    assert len(bundles) == 1 and bundles[0].startswith("kl-")
    d = tmp_path / "evidence" / bundles[0]
    with open(d / "alert.json") as f:
        alert = json.load(f)
    # the triggering metric window + its per-source readings ride along
    assert alert["rule"] == "kl" and alert["metric_window"]
    assert alert["metric_window"][-1]["value"] == 3.0
    assert "trainer|train/approx_kl{mfc=actor_train}" in alert["sources"]
    with open(d / "traces.json") as f:
        assert json.load(f)["pinned_trace_ids"] == ["trace-a", "trace-b"]
    # the fleet-wide flight dump was requested INTO the bundle
    assert cap["flight"] == [str(d)]
    # a second fire past cooldown skips capture at the bundle cap
    at(5.0)
    s.feed("trainer", {"train/approx_kl{mfc=actor_train}": 0.0}, now=5.0)
    s.tick(5.0)
    at(40.0)
    s.feed("trainer", {"train/approx_kl{mfc=actor_train}": 3.0}, now=40.0)
    s.tick(40.0)
    at(45.0)
    s.tick(45.0)
    assert s.states()["kl"]["fires"] == 2
    assert len(os.listdir(tmp_path / "evidence")) == 1
    assert s.registry.snapshot()["counters"][
        "sentinel/evidence_skipped"] == 1.0


def test_critical_publishes_autoscale_inhibit(tmp_path, tmp_name_resolve):
    from areal_tpu.system import autoscaler

    # real inhibit_fn (writes names.autoscale_inhibit), fake clocks
    t = {"wall": 1_000.0}
    s = Sentinel(
        SentinelConfig(enabled=True, eval_interval_secs=0.1,
                       inhibit_secs=120.0),
        "sentexp", "t0", rules=parse_rules([THRESH]),
        alerts_path=str(tmp_path / "alerts.jsonl"),
        evidence_dir=None,
        clock=lambda: t.setdefault("mono", 0.0) or t["mono"],
        wall=lambda: t["wall"],
        flight_fn=lambda d: None,
    )
    t["mono"] = 0.0
    s.feed("trainer", {"train/approx_kl": 9.0}, now=0.0)
    s.tick(0.0)
    t["mono"] = 2.5
    s.tick(2.5)
    rec = autoscaler.read_inhibit("sentexp", "t0", wall=lambda: 1_010.0)
    assert rec is not None and rec["rule"] == "kl"
    # expired hints read as absent — a resolved incident cannot pin the
    # fleet forever
    assert autoscaler.read_inhibit("sentexp", "t0",
                                   wall=lambda: 1_200.0) is None
    # and an inhibited signal suppresses every scale-up reason
    core = autoscaler.AutoscalerCore(
        autoscaler.AutoscaleConfig(enabled=True, max_servers=4),
        clock=lambda: 0.0,
    )
    hot = dict(current_size=1, utilization=0.99, queue_depth=50.0)
    assert core._up_reasons(autoscaler.FleetSignals(**hot)) != []
    assert core._up_reasons(
        autoscaler.FleetSignals(**hot, inhibited=True)) == []


def test_pause_action_is_gated_by_allow_pause(tmp_path):
    rule = dict(THRESH, action="pause")
    s, at, cap = make_sentinel(tmp_path, parse_rules([rule]))
    at(0.0)
    s.feed("trainer", {"train/approx_kl": 9.0}, now=0.0)
    s.tick(0.0)
    at(2.5)
    s.tick(2.5)
    assert cap["pause"] == 0  # allow_pause defaults False
    assert read_alerts(tmp_path)[0]["pause_requested"] is False
    cfg = SentinelConfig(enabled=True, eval_interval_secs=0.1,
                         allow_pause=True)
    s2, at2, cap2 = make_sentinel(tmp_path / "p2", parse_rules([rule]),
                                  cfg=cfg)
    at2(0.0)
    s2.feed("trainer", {"train/approx_kl": 9.0}, now=0.0)
    s2.tick(0.0)
    at2(2.5)
    s2.tick(2.5)
    assert cap2["pause"] == 1
    assert read_alerts(tmp_path / "p2")[0]["pause_requested"] is True


# ---------------------------------------------------------------------------
# disabled contract + aggregator hosting
# ---------------------------------------------------------------------------


def test_sentinel_owns_no_threads_or_sockets(tmp_path):
    """The engine is driven entirely by its host's existing loop: even
    ENABLED it spawns nothing — and through a full feed → fire →
    resolve cycle the process thread set is unchanged."""
    before = set(threading.enumerate())
    s, at, cap = make_sentinel(tmp_path, parse_rules([THRESH]))
    at(0.0)
    s.feed("trainer", {"train/approx_kl": 9.0}, now=0.0)
    s.tick(0.0)
    at(2.5)
    s.tick(2.5)
    at(5.0)
    s.feed("trainer", {"train/approx_kl": 0.0}, now=5.0)
    s.tick(5.0)
    s.close()
    assert set(threading.enumerate()) == before


def test_disabled_mode_leaves_aggregator_untouched(tmp_name_resolve,
                                                   tmp_path):
    """sentinel=None (the disabled path): no sentinel row on the merged
    scrape, no alerts families, no alerts.jsonl — bit-identical to a
    build without the sentinel."""
    agg = telemetry.TelemetryAggregator(
        "sentexp", "t0", jsonl_path=str(tmp_path / "telemetry.jsonl")
    )
    try:
        assert agg.sentinel is None
        body = agg.render_prometheus()
        assert "areal_alerts" not in body
        assert "sentinel" not in body
    finally:
        agg.close()
    assert not (tmp_path / "alerts.jsonl").exists()
    # ...and the master constructs no sentinel without the config flag
    from areal_tpu.system.master_worker import MasterWorkerConfig

    assert MasterWorkerConfig().sentinel.enabled is False


def test_aggregator_hosts_sentinel_end_to_end(tmp_name_resolve, tmp_path):
    """The real wiring: a worker's TelemetryPusher flushes a divergence
    gauge into the aggregator; the hosted sentinel trips the rule and the
    MERGED Prometheus endpoint carries areal_alerts_total{rule,severity}
    + areal_alert_active."""
    rules = parse_rules([
        {"id": "kl_hot", "metric": "train/approx_kl", "op": "gt",
         "value": 1.0, "for": 0.05, "cooldown": 60,
         "severity": "critical"},
    ])
    s = Sentinel(
        SentinelConfig(enabled=True, eval_interval_secs=0.01),
        "sentexp", "t0", rules=rules,
        alerts_path=str(tmp_path / "alerts.jsonl"),
        evidence_dir=str(tmp_path / "evidence"),
    )
    agg = telemetry.TelemetryAggregator(
        "sentexp", "t0", jsonl_path=str(tmp_path / "telemetry.jsonl"),
        sentinel=s,
    )
    reg = telemetry.TelemetryRegistry()
    pusher = telemetry.TelemetryPusher(
        reg, "sentexp", "t0", "trainer", 0, flush_interval_secs=60.0
    )
    try:
        # evidence bundles pin recent stitched traces via the REAL
        # stitcher the aggregator handed over
        assert s.stitcher is agg.stitcher
        reg.set_gauge("train/approx_kl{mfc=actor_train}", 4.0)
        assert pusher.flush()
        deadline = telemetry.time.monotonic() + 10
        while telemetry.time.monotonic() < deadline:
            if s.states()["kl_hot"]["state"] == "firing":
                break
            pusher.flush()
            telemetry.time.sleep(0.02)
        assert s.states()["kl_hot"]["state"] == "firing"
        body = agg.render_prometheus()
        assert ('areal_alerts_total{rule="kl_hot",severity="critical",'
                'worker_index="0",worker_kind="sentinel"} 1') in body
        assert 'areal_alert_active{rule="kl_hot"' in body
        recs = read_alerts(tmp_path)
        assert recs and recs[0]["rule"] == "kl_hot"
        assert recs[0].get("evidence_dir")
        # the evidence request armed the fleet-wide flight-dump flag
        raw = name_resolve.get(
            names.flight_dump_trigger("sentexp", "t0"))
        assert json.loads(raw)["dir"] == recs[0]["evidence_dir"]
    finally:
        pusher.close()
        agg.close()


# ---------------------------------------------------------------------------
# jax-free operator CLI (tools/perf_probe.py)
# ---------------------------------------------------------------------------


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PERF_PROBE = os.path.join(REPO, "tools", "perf_probe.py")


def _perf_probe_commands():
    import importlib.util

    spec = importlib.util.spec_from_file_location("_perf_probe", PERF_PROBE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.COMMANDS


def _no_jax_env(tmp_path, **extra):
    """An environment in which ``import jax`` raises: a poisoned module
    at the head of the path."""
    poison = tmp_path / "poison"
    poison.mkdir(exist_ok=True)
    (poison / "jax.py").write_text(
        "raise ImportError('perf_probe must not import jax')\n")
    return dict(os.environ, PYTHONPATH=str(poison), **extra)


@pytest.mark.parametrize("command", _perf_probe_commands())
def test_perf_probe_is_jax_free(tmp_path, command):
    """Every command is for a live run and needs operands: without them
    it prints the usage and exits 1 — with jax unimportable."""
    import subprocess
    import sys as _sys

    out = subprocess.run(
        [_sys.executable, PERF_PROBE, command], capture_output=True,
        text=True, cwd=REPO, env=_no_jax_env(tmp_path), timeout=60,
    )
    assert out.returncode == 1, out.stderr
    assert f"missing operand for {command!r}" in out.stderr
    assert "Usage: python tools/perf_probe.py" in out.stderr
    assert "must not import jax" not in out.stderr


def test_perf_probe_alerts_and_silence_cli(tmp_path):
    """`alerts` filters a recorded stream and `silence` writes the
    name-resolve key — with jax unimportable."""
    import subprocess
    import sys as _sys

    stream = tmp_path / "alerts.jsonl"
    with open(stream, "w") as f:
        f.write(json.dumps({"event": "firing", "rule": "kl_blowup",
                            "severity": "critical", "metric":
                            "train/approx_kl", "value": 2.0,
                            "ts": 1000.0}) + "\n")
        f.write(json.dumps({"event": "firing", "rule": "reward_drift",
                            "severity": "warn", "metric":
                            "train/task_reward", "value": 0.1,
                            "ts": 1001.0}) + "\n")
    env = _no_jax_env(tmp_path,
                      AREAL_NAME_RESOLVE_ROOT=str(tmp_path / "nr"))
    out = subprocess.run(
        [_sys.executable, PERF_PROBE, "alerts", str(stream), "critical"],
        capture_output=True, text=True, cwd=REPO, env=env,
    )
    assert out.returncode == 0, out.stderr
    assert "kl_blowup" in out.stdout
    assert "reward_drift" not in out.stdout
    assert "(1/2 records" in out.stdout
    out = subprocess.run(
        [_sys.executable, PERF_PROBE, "silence",
         "sentexp", "t0", "kl_blowup", "10m"],
        capture_output=True, text=True, cwd=REPO, env=env,
    )
    assert out.returncode == 0, out.stderr
    assert "600s" in out.stdout
    repo = name_resolve.NfsNameRecordRepo(str(tmp_path / "nr"))
    rec = json.loads(repo.get(
        names.sentinel_silence("sentexp", "t0", "kl_blowup")))
    assert rec["duration_secs"] == 600.0


# ---------------------------------------------------------------------------
# training-dynamics export (the series the rules consume)
# ---------------------------------------------------------------------------


def test_actor_loss_emits_divergence_stats():
    import jax.numpy as jnp

    from areal_tpu.algorithms import ppo_functional as F

    lp = jnp.array([[-1.0, -2.0, -1.5, 0.0]])
    old = jnp.array([[-1.2, -1.8, -1.5, 0.0]])
    prox = jnp.array([[-1.1, -1.9, -1.5, 0.0]])
    adv = jnp.array([[0.5, -0.5, 1.0, 0.0]])
    mask = jnp.array([[True, True, True, False]])
    # default loss_scale ⇒ denom = masked token count ⇒ stats are means
    # (the PPO interface passes loss_scale=1 and re-normalizes by the
    # global action-token count instead)
    _, st = F.actor_loss(lp, old, adv, mask, proximal_logprobs=prox,
                         behav_imp_weight_cap=1.05)
    # k1 approx-KL of current vs BEHAVIOUR policy over masked tokens
    assert abs(float(st["approx_kl"]) - (-0.2 + 0.2 + 0.0) / 3) < 1e-6
    # sampled-token entropy estimate: −mean(logprob)
    assert abs(float(st["entropy"]) - 1.5) < 1e-6
    # exp(prox−behav) = e^0.1 ≈ 1.105 > cap at token 0 → 1/3 of the mass
    assert abs(float(st["behav_tail"]) - 1 / 3) < 1e-6
    # without a decoupled center the tail is identically zero
    _, st2 = F.actor_loss(lp, old, adv, mask)
    assert float(st2["behav_tail"]) == 0.0


def test_trainer_exports_train_gauges(tmp_name_resolve):
    from areal_tpu.system.trainer_worker import TrainerWorker

    telemetry.configure("sentexp", "t0", "trainer", 0,
                        TelemetryConfig(enabled=True), push=False)
    try:
        w = TrainerWorker.__new__(TrainerWorker)
        w._export_train_stats("actor_train", {
            "approx_kl": 0.02, "entropy": 3.1, "grad_norm": 1.7,
            "actor_loss": -0.4, "n_ppo_steps": 4.0,
            "bad": float("nan"),  # non-finite values never export
        })
        snap = telemetry.get().snapshot()
        g = snap["gauges"]
        assert g["train/approx_kl{mfc=actor_train}"] == 0.02
        assert g["train/entropy{mfc=actor_train}"] == 3.1
        assert g["train/actor_loss{mfc=actor_train}"] == -0.4
        assert "train/bad{mfc=actor_train}" not in g
        # divergence signatures additionally get a distribution view
        assert snap["hists"]["train/grad_norm_dist{mfc=actor_train}"][
            "count"] == 1
    finally:
        telemetry.shutdown()


# ---------------------------------------------------------------------------
# compile-aware liveness (base/compile_watch.py, ISSUE 20 drive-by)
# ---------------------------------------------------------------------------


def _stalled_rules():
    raw = next(r for r in DEFAULT_RULES if r["id"] == "trainer_stalled")
    return parse_rules([dict(raw)])


def test_trainer_stalled_fires_in_minutes_not_half_an_hour(tmp_path):
    """The drive-by regression: the old fix was a blanket 1800s grace
    that hid every genuinely-wedged trainer for half an hour. With the
    compile observatory the grace is 300s + compile-aware suppression —
    a wedged, NON-compiling trainer alerts in minutes."""
    s, at, cap = make_sentinel(tmp_path, _stalled_rules())
    at(0.0)
    s.feed("trainer", {}, {"train/optimizer_steps": 5.0}, now=0.0)
    s.tick(0.0)
    # wedged from t=0 on; well before the 300s window: quiet
    at(200.0)
    s.feed("trainer", {}, {"train/optimizer_steps": 5.0}, now=200.0)
    s.tick(200.0)
    assert s.states()["trainer_stalled"]["state"] == "ok"
    # past 300s of no progress, no compile in flight: fires — far
    # earlier than the old 1800s blanket grace would have allowed
    at(310.0)
    s.feed("trainer", {}, {"train/optimizer_steps": 5.0}, now=310.0)
    s.tick(310.0)
    assert s.states()["trainer_stalled"]["state"] == "firing"
    recs = read_alerts(tmp_path)
    assert recs and recs[0]["rule"] == "trainer_stalled"
    assert recs[0]["severity"] == "critical"


def test_trainer_stalled_suppressed_while_compile_inflight(tmp_path):
    """A trainer sitting inside a warmup XLA compile makes no optimizer
    steps but is NOT wedged: the live compile/inflight gauge explains
    the absence and the rule must stay quiet until the compile drains
    AND the silence persists."""
    s, at, cap = make_sentinel(tmp_path, _stalled_rules())
    at(0.0)
    s.feed("trainer", {"compile/inflight": 1.0},
           {"train/optimizer_steps": 5.0}, now=0.0)
    s.tick(0.0)
    # 20 minutes inside the compile, zero steps: suppressed throughout
    for t in (200.0, 400.0, 800.0, 1200.0):
        at(t)
        s.feed("trainer", {"compile/inflight": 1.0},
               {"train/optimizer_steps": 5.0}, now=t)
        s.tick(t)
        assert s.states()["trainer_stalled"]["state"] == "ok"
    assert read_alerts(tmp_path) == []
    # the compile drains but the trainer STAYS stuck: once the silence
    # outlives `for:` with no compile in flight, it fires
    at(1210.0)
    s.feed("trainer", {"compile/inflight": 0.0},
           {"train/optimizer_steps": 5.0}, now=1210.0)
    s.tick(1210.0)
    assert s.states()["trainer_stalled"]["state"] == "firing"
    # ...and a compiled-then-progressing trainer would have resolved:
    at(1220.0)
    s.feed("trainer", {"compile/inflight": 0.0},
           {"train/optimizer_steps": 6.0}, now=1220.0)
    s.tick(1220.0)
    assert s.states()["trainer_stalled"]["state"] == "ok"
    events = [r["event"] for r in read_alerts(tmp_path)]
    assert events == ["firing", "resolved"]


def test_name_resolve_inflight_flag_rolls_fire_back(
        tmp_path, tmp_name_resolve):
    """The telemetry-flush gap: a worker wedged INSIDE a compile stops
    flushing metrics (no compile/inflight gauge arrives) but its
    heartbeat thread still rewrites names.compile_inflight. A fresh flag
    rolls the fire back to pending exactly like a silence; a stale flag
    (dead worker's ghost) does not suppress."""
    s, at, cap = make_sentinel(tmp_path, _stalled_rules())
    key = names.compile_inflight("sentexp", "t0", "trainer/0")
    # wall clock starts at 1000.0 in make_sentinel
    name_resolve.add(key, json.dumps({"ts": 995.0}), replace=True,
                     delete_on_exit=False)
    at(310.0)
    s.tick(310.0)
    st = s.states()["trainer_stalled"]
    assert st["state"] == "pending" and st["fires"] == 0
    assert read_alerts(tmp_path) == []
    snap = s.registry.snapshot()
    assert snap["counters"][
        "sentinel/compile_suppressed{rule=trainer_stalled}"] == 1.0
    # the flag goes stale (heartbeat stopped rewriting it >60s ago):
    # a ghost must not suppress — the next tick fires for real
    at(500.0, 1500.0)
    s.tick(500.0)
    st = s.states()["trainer_stalled"]
    assert st["state"] == "firing" and st["fires"] == 1
    assert [r["rule"] for r in read_alerts(tmp_path)] == ["trainer_stalled"]
