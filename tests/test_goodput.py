"""Goodput ledger (system/goodput.py, docs/observability.md §Goodput).

Fake clocks everywhere for the ledger state machine (transitions sum to
wall clock, counters monotonic, export rate-limiting) and in-process fakes
for the aggregator fleet stitch. The disabled path is pinned
bit-identical: a null ledger must leave the Prometheus scrape byte-equal
to a build without the ledger.
"""

import json

import pytest

from areal_tpu.api.train_config import GoodputConfig, TelemetryConfig
from areal_tpu.base import monitor, telemetry
from areal_tpu.system import goodput

pytestmark = pytest.mark.goodput


class FakeClock:
    def __init__(self):
        self.now = 100.0

    def __call__(self):
        return self.now

    def advance(self, dt):
        self.now += dt


def make_ledger(**kw):
    clock = FakeClock()
    reg = telemetry.TelemetryRegistry()
    led = goodput.GoodputLedger(reg, clock=clock,
                                export_interval_secs=0.0, **kw)
    return led, clock, reg


# ---------------------------------------------------------------------------
# ledger state machine
# ---------------------------------------------------------------------------


def test_partition_sums_to_wall_clock():
    led, clock, _ = make_ledger()
    clock.advance(2.0)  # idle (the base state)
    with led.state("compute"):
        clock.advance(3.0)
        with led.state("comm"):  # nested: publish inside an MFC
            clock.advance(1.0)
        clock.advance(0.5)  # back in compute after the nested exit
    clock.advance(1.5)  # idle again
    led.flush()
    t = led.totals()
    assert t["compute"] == pytest.approx(3.5)
    assert t["comm"] == pytest.approx(1.0)
    assert t["idle"] == pytest.approx(3.5)
    assert t["data_wait"] == 0.0
    # THE invariant: a wall-partition ledger's states sum to elapsed wall
    assert sum(t.values()) == pytest.approx(8.0)


def test_state_restored_on_exception():
    led, clock, _ = make_ledger()
    with pytest.raises(RuntimeError):
        with led.state("compute"):
            clock.advance(1.0)
            raise RuntimeError("mfc failed")
    clock.advance(2.0)
    led.flush()
    t = led.totals()
    assert t["compute"] == pytest.approx(1.0)
    assert t["idle"] == pytest.approx(2.0)  # restored despite the raise


def test_exported_counters_monotonic_deltas():
    led, clock, reg = make_ledger()
    with led.state("compute"):
        clock.advance(4.0)
    led.flush()
    c = reg.snapshot()["counters"]
    assert c["goodput/secs{state=compute}"] == pytest.approx(4.0)
    # zero-time states export nothing (no noise families on the scrape)
    assert "goodput/secs{state=data_wait}" not in c
    # more work only ever INCREASES the counter (delta export)
    with led.state("compute"):
        clock.advance(1.0)
    led.flush()
    c2 = reg.snapshot()["counters"]
    assert c2["goodput/secs{state=compute}"] == pytest.approx(5.0)
    assert c2.get("goodput/secs{state=idle}", 0.0) \
        >= c.get("goodput/secs{state=idle}", 0.0)


def test_export_rate_limited_to_interval():
    clock = FakeClock()
    reg = telemetry.TelemetryRegistry()
    led = goodput.GoodputLedger(reg, clock=clock,
                                export_interval_secs=10.0)
    with led.state("compute"):
        clock.advance(1.0)
    # under the interval: accrued host-side, nothing exported yet
    assert "goodput/secs{state=compute}" not in reg.snapshot()["counters"]
    clock.advance(10.0)
    led.poll()
    assert reg.snapshot()["counters"]["goodput/secs{state=compute}"] \
        == pytest.approx(1.0)
    # flush() exports unconditionally (shutdown path)
    with led.state("comm"):
        clock.advance(0.5)
    led.flush()
    assert reg.snapshot()["counters"]["goodput/secs{state=comm}"] \
        == pytest.approx(0.5)


def test_accrual_only_mode_for_concurrent_workers():
    clock = FakeClock()
    reg = telemetry.TelemetryRegistry()
    led = goodput.GoodputLedger(reg, clock=clock,
                                export_interval_secs=0.0,
                                initial_state=None)
    # overlapping task windows (N concurrent rollouts): task-seconds,
    # deliberately NOT clamped to wall clock
    led.add("comm", 3.0)
    led.add("comm", 2.0)
    led.add("data_wait", 4.0)
    clock.advance(1.0)
    led.poll()  # no current state: poll only exports, accrues nothing
    led.flush()
    t = led.totals()
    assert t["comm"] == pytest.approx(5.0)
    assert t["data_wait"] == pytest.approx(4.0)
    assert t["idle"] == 0.0
    c = reg.snapshot()["counters"]
    assert c["goodput/secs{state=comm}"] == pytest.approx(5.0)


def test_overlap_family_kept_out_of_the_partition():
    """Work racing the partition owner (a genserver weight update during
    decode) accrues in goodput/overlap_secs — folding it into the
    partition counters would make states sum past wall clock, deflating
    every rate()-derived fraction and generation-side fleet goodput."""
    led, clock, reg = make_ledger()
    with led.state("compute"):
        clock.advance(4.0)
        led.add_overlap("comm", 2.5)  # overlaps the compute window
    led.flush()
    t = led.totals()
    # the partition still sums to wall clock exactly
    assert sum(t.values()) == pytest.approx(4.0)
    c = reg.snapshot()["counters"]
    assert c["goodput/overlap_secs{state=comm}"] == pytest.approx(2.5)
    assert "goodput/secs{state=comm}" not in c
    # ...and the fleet stitch ignores the overlap family entirely
    fg = goodput.FleetGoodput(clock=FakeClock())
    g = fg.update("generation_server:0", {
        "goodput/secs{state=compute}": 4.0,
        "goodput/overlap_secs{state=comm}": 2.5,
    })
    assert g["fleet/goodput{side=generation}"] == pytest.approx(1.0)


def test_disabled_contract_scrape_bit_identical():
    # the registry a worker would scrape, with ordinary metrics on it
    reg = telemetry.TelemetryRegistry()
    reg.inc("genserver/decode_chunks", 3)
    reg.set_gauge("genserver/weight_version", 2)
    before = telemetry.render_prometheus(reg.snapshot(reset=False))
    led = goodput.make_ledger(GoodputConfig(enabled=False), reg)
    assert led is goodput.NULL_LEDGER
    with led.state("compute"):
        pass
    led.add("comm", 5.0)
    led.enter("data_wait")
    led.poll()
    led.flush()
    assert led.totals() == {}
    after = telemetry.render_prometheus(reg.snapshot(reset=False))
    assert after == before  # byte-equal: zero new families, zero samples
    # an enabled config with a DISABLED telemetry sink also nulls out
    # (nowhere to export — the validate_config contract, belt+braces)
    assert goodput.make_ledger(
        GoodputConfig(enabled=True), telemetry.NULL
    ) is goodput.NULL_LEDGER


# ---------------------------------------------------------------------------
# live MFU: peak resolution + degradation
# ---------------------------------------------------------------------------


def test_resolve_peak_override_and_table():
    assert goodput.resolve_peak_flops(
        GoodputConfig(peak_flops_override=5e12), "TFRT_CPU_0"
    ) == 5e12
    assert goodput.resolve_peak_flops(
        GoodputConfig(), "TPU v5 lite") == 197e12
    assert goodput.resolve_peak_flops(GoodputConfig(), "cpu") is None
    # a TPU kind the table does not know is an error, never a silent None
    with pytest.raises(KeyError, match="TPU v9"):
        goodput.resolve_peak_flops(GoodputConfig(), "TPU v9")


def test_mfu_emitter_degrades_on_unknown_peak():
    reg = telemetry.TelemetryRegistry()
    m = goodput.MfuEmitter(reg, None, tflops_name="train/achieved_tflops",
                           mfu_name="train/mfu", context="trainer")
    assert not m._warned
    m.emit(10e12)
    assert m._warned  # warned (once) on the first degraded emit
    m.emit(20e12)
    g = reg.snapshot()["gauges"]
    assert g["train/achieved_tflops"] == pytest.approx(20.0)
    # the satellite contract: NO mfu=0.0 (a hard zero reads as a real
    # collapse to any rolling-baseline sentinel rule)
    assert "train/mfu" not in g


def test_mfu_emitter_with_known_peak():
    reg = telemetry.TelemetryRegistry()
    m = goodput.MfuEmitter(reg, 100e12, tflops_name="train/achieved_tflops",
                           mfu_name="train/mfu")
    m.emit(25e12)
    g = reg.snapshot()["gauges"]
    assert g["train/achieved_tflops"] == pytest.approx(25.0)
    assert g["train/mfu"] == pytest.approx(0.25)
    m.emit(0.0)  # no-op: a zero sample must not zero the gauges
    assert reg.snapshot()["gauges"]["train/mfu"] == pytest.approx(0.25)


def test_bench_flops_accounting_parity():
    """The live MFU gauges divide monitor.train_flops_6nt by
    device_peak_flops: pin both against the 6·N·T formula and the peak
    table, keyed by the exact ``device_kind`` jax reports."""
    n_params, steps, total, dt, n_chips = 494_032_768, 3, 30_000, 4.2, 1
    flops_inline = 6.0 * n_params * (steps * total)
    assert monitor.train_flops_6nt(n_params, steps * total) == flops_inline
    for kind, want in [("TPU v5 lite", 197e12), ("TPU v5e", 197e12),
                       ("TPU v5", 459e12), ("TPU v4", 275e12),
                       ("TPU v6 lite", 918e12)]:
        assert monitor.device_peak_flops(kind) == want
    assert monitor.device_peak_flops("cpu") is None
    # substring look-alikes no longer match: unknown TPU kinds raise
    for kind in ("TPU v5 lite chip", "TPU v4 x2", "TPU v7"):
        with pytest.raises(KeyError):
            monitor.device_peak_flops(kind)
    mfu_old = flops_inline / dt / n_chips / 197e12
    mfu_new = (monitor.train_flops_6nt(n_params, steps * total)
               / dt / n_chips / monitor.device_peak_flops("TPU v5 lite"))
    assert mfu_new == pytest.approx(mfu_old)


def test_master_counts_mfc_flops_over_the_token_key():
    """The master's per-MFC FLOPs come from the TOKEN lengths, whichever
    order the sample's keys iterate in (a scalar key such as rewards has
    length 1 per sample — the first chip run counted those)."""
    import types

    from areal_tpu.api.data import SequenceSample
    from areal_tpu.api.dfg import MFCInterfaceType
    from areal_tpu.system.master_worker import MasterWorker

    info = {"n_layers": 2, "hidden_dim": 64, "q_dim": 64, "kv_dim": 32,
            "intermediate_dim": 128, "vocab_size": 1000, "is_critic": False,
            "moe": None, "remat": False}
    fake = types.SimpleNamespace(
        _model_info={"roles": {"actor": info}}, _flops=monitor.FlopsCounter()
    )
    metas = [
        SequenceSample(
            ids=[i], keys={"rewards", "packed_input_ids"},
            seqlens={"rewards": [[1]], "packed_input_ids": [[100]]},
            data=None,
        ) for i in range(4)
    ]
    node = types.SimpleNamespace(
        model_name="actor", interface_type=MFCInterfaceType.TRAIN_STEP
    )
    MasterWorker._count_mfc_flops(fake, node, metas)
    want = monitor.FlopsCounter()
    want.add_train(types.SimpleNamespace(**info), 400.0, 100.0)
    assert fake._flops.pop() == want.pop() > 0


def test_validate_config_gates_goodput():
    from areal_tpu.api import cli_args
    from areal_tpu.experiments.ppo_math_exp import PPOMATHConfig

    cfg = PPOMATHConfig()
    cfg.goodput.enabled = True
    with pytest.raises(cli_args.ConfigError, match="telemetry"):
        cli_args.validate_config(cfg)
    cfg.telemetry.enabled = True
    cli_args.validate_config(cfg)
    cfg.goodput.export_interval_secs = 0.0
    with pytest.raises(cli_args.ConfigError, match="export_interval"):
        cli_args.validate_config(cfg)
    cfg.goodput.export_interval_secs = 1.0
    cfg.goodput.peak_flops_override = -1.0
    with pytest.raises(cli_args.ConfigError, match="peak_flops_override"):
        cli_args.validate_config(cfg)


# ---------------------------------------------------------------------------
# fleet stitching
# ---------------------------------------------------------------------------


def test_fleet_goodput_split_and_exclusions():
    fg = goodput.FleetGoodput(clock=FakeClock())
    g = fg.update("trainer:0", {
        "goodput/secs{state=compute}": 8.0,
        "goodput/secs{state=idle}": 2.0,
        "train/tokens": 999.0,  # non-ledger counters are ignored
    })
    assert g["fleet/goodput"] == pytest.approx(0.8)
    assert g["fleet/goodput{side=trainer}"] == pytest.approx(0.8)
    assert "fleet/goodput{side=generation}" not in g
    g = fg.update("generation_server:0", {
        "goodput/secs{state=compute}": 5.0,
        "goodput/secs{state=idle}": 5.0,
    })
    assert g["fleet/goodput"] == pytest.approx(13.0 / 20.0)
    assert g["fleet/goodput{side=trainer}"] == pytest.approx(0.8)
    assert g["fleet/goodput{side=generation}"] == pytest.approx(0.5)
    assert g["fleet/goodput_workers"] == 2.0
    # rollout counters are task-seconds under concurrency — visible
    # per-worker on the scrape but NEVER folded into chip goodput
    g = fg.update("rollout:0", {"goodput/secs{state=comm}": 100.0})
    assert g["fleet/goodput"] == pytest.approx(13.0 / 20.0)
    assert g["fleet/goodput_workers"] == 2.0
    # a snapshot without ledger counters derives nothing
    assert fg.update("trainer:0", {"trainer/store_size": 4.0}) is None
    # the registry mirrors the latest gauges (the aggregator's fleet row)
    assert fg.gauges()["fleet/goodput"] == pytest.approx(13.0 / 20.0)


def test_fleet_goodput_is_windowed_not_since_start():
    """A since-start average's sensitivity decays with run length; the
    stitch must report the LAST WINDOW so a late-run idle fleet moves
    the gauge (and the goodput_collapse rule) immediately."""
    clock = FakeClock()
    fg = goodput.FleetGoodput(clock=clock, window_secs=100.0,
                              expiry_secs=1e9)
    # a long healthy history: fully busy for 10_000s
    busy = 0.0
    for _ in range(100):
        clock.advance(100.0)
        busy += 100.0
        g = fg.update("trainer:0",
                      {"goodput/secs{state=compute}": busy})
    assert g["fleet/goodput"] == pytest.approx(1.0)
    # the fleet goes FULLY idle for one window: the gauge collapses to
    # ~0 even though the since-start average would still read ~0.99
    idle = 0.0
    for _ in range(10):
        clock.advance(10.0)
        idle += 10.0
        g = fg.update("trainer:0", {
            "goodput/secs{state=compute}": busy,
            "goodput/secs{state=idle}": idle,
        })
    assert g["fleet/goodput"] < 0.05, g


def test_fleet_goodput_restart_rebaselines_and_departed_expire():
    clock = FakeClock()
    fg = goodput.FleetGoodput(clock=clock, window_secs=1e9,
                              expiry_secs=60.0)
    fg.update("generation_server:0", {"goodput/secs{state=compute}": 50.0,
                                      "goodput/secs{state=idle}": 50.0})
    clock.advance(10.0)
    g = fg.update("trainer:0", {"goodput/secs{state=compute}": 10.0})
    assert g["fleet/goodput_workers"] == 2.0
    assert g["fleet/goodput{side=generation}"] == pytest.approx(0.5)
    # the gen server RESTARTS (cumulative counters reset backward): its
    # baseline restarts — fresh totals, not bogus negative deltas
    clock.advance(10.0)
    g = fg.update("generation_server:0",
                  {"goodput/secs{state=compute}": 3.0,
                   "goodput/secs{state=idle}": 1.0})
    assert g["fleet/goodput{side=generation}"] == pytest.approx(0.75)
    # ...then it is evicted: past expiry_secs without a report its
    # frozen totals drop out of the fractions entirely
    clock.advance(120.0)
    g = fg.update("trainer:0", {"goodput/secs{state=compute}": 20.0})
    assert g["fleet/goodput_workers"] == 1.0
    assert "fleet/goodput{side=generation}" not in g
    assert g["fleet/goodput"] == pytest.approx(1.0)
    # ...and the registry WITHDRAWS the dead side's gauge (a frozen
    # last value on the scrape would describe a fleet that is gone)
    assert "fleet/goodput{side=generation}" not in fg.gauges()
    assert "fleet/goodput{side=trainer}" in fg.gauges()


def _wait_until(pred, timeout=10.0, interval=0.02):
    import time

    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(interval)
    return False


def test_aggregator_merges_fleet_goodput_row(tmp_name_resolve, tmp_path):
    """The TelemetryAggregator with a FleetGoodput derives the fleet row
    onto the merged scrape and telemetry.jsonl; without one (the
    disabled default) the same ingest renders zero goodput families."""
    jsonl = str(tmp_path / "telemetry.jsonl")

    class _FakeSentinel:
        # the minimal surface the aggregator touches
        stitcher = object()
        registry = telemetry.TelemetryRegistry()
        feeds = []

        def feed(self, worker, gauges, counters=None):
            self.feeds.append((worker, dict(gauges)))

        def tick(self):
            pass

        def close(self):
            pass

    fake_sentinel = _FakeSentinel()
    agg = telemetry.TelemetryAggregator(
        "gp", "t", jsonl_path=jsonl, goodput=goodput.FleetGoodput(),
        sentinel=fake_sentinel,
    )
    p = None
    try:
        reg = telemetry.TelemetryRegistry()
        reg.inc("goodput/secs{state=compute}", 9.0)
        reg.inc("goodput/secs{state=idle}", 1.0)
        p = telemetry.TelemetryPusher(reg, "gp", "t", "trainer", 0,
                                      flush_interval_secs=3600)
        assert p.flush()
        assert _wait_until(lambda: len(agg.state) == 1)
        text = agg.render_prometheus()
        assert ('areal_goodput_secs_total{state="compute",'
                'worker_index="0",worker_kind="trainer"} 9') in text
        assert ('areal_fleet_goodput{worker_index="0",'
                'worker_kind="fleet"} 0.9') in text
        assert ('areal_fleet_goodput{side="trainer",worker_index="0",'
                'worker_kind="fleet"} 0.9') in text
        # the sentinel feed carries ONLY unlabeled keys: the engine
        # folds {side=...} variants into the same family, and averaging
        # the overall with the per-side splits would mis-weight the
        # sides (and step-change when a side appears/expires)
        fleet_feeds = [g for w, g in fake_sentinel.feeds
                       if w == "fleet:0"]
        assert fleet_feeds, fake_sentinel.feeds
        assert all("{" not in k for g in fleet_feeds for k in g)
        assert any("fleet/goodput" in g for g in fleet_feeds)
    finally:
        if p is not None:
            p.close()
        agg.close()
    # the fleet record landed in telemetry.jsonl alongside the snapshots
    with open(jsonl) as f:
        recs = [json.loads(ln) for ln in f if ln.strip()]
    fleet = [r for r in recs if r["worker"] == "fleet:0"]
    assert fleet and fleet[0]["gauges"]["fleet/goodput"] \
        == pytest.approx(0.9)


def test_aggregator_without_goodput_renders_no_fleet_row(tmp_name_resolve):
    agg = telemetry.TelemetryAggregator("gp2", "t", jsonl_path=None)
    p = None
    try:
        reg = telemetry.TelemetryRegistry()
        reg.inc("goodput/secs{state=compute}", 9.0)
        p = telemetry.TelemetryPusher(reg, "gp2", "t", "trainer", 0,
                                      flush_interval_secs=3600)
        assert p.flush()
        assert _wait_until(lambda: len(agg.state) == 1)
        assert "areal_fleet_goodput" not in agg.render_prometheus()
    finally:
        if p is not None:
            p.close()
        agg.close()
