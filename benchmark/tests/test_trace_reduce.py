"""The trace reduction on plain planes with known numbers, and on a small
trace recorded on the chip and kept beside this file."""

import os

import pytest

from benchmark import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))


def planes():
    # chip 0: a `while` op [1.0, 3.0] holding two fusions [1.0,1.5] and
    # [2.0,2.75], then a flash call [4.0, 5.0]; idle [0,1), [3,4), [5,6].
    ops0 = [(1.0, 3.0, "while.1"), (1.0, 1.5, "fusion.1"),
            (2.0, 2.75, "fusion.2"), (4.0, 5.0, "flash_fwd.3")]
    # chip 1: busy [0.5, 1.5] only
    ops1 = [(0.5, 1.5, "fusion.9")]
    host = [(0.0, 1.1, "bench/pack"), (0.2, 0.4, "bench/inner"),
            (2.9, 4.1, "bench/dispatch"), (0.0, 6.0, "not-ours")]
    return [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops0},
            {"name": "XLA Modules", "events": [(0.0, 6.0, "jit_step")]}]},
        {"name": "/device:TPU:1", "lines": [
            {"name": "XLA Ops", "events": ops1}]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": host}]},
    ]


def test_known_busy_idle_per_op_and_gap_numbers():
    red = tr.reduce_planes(planes(), t_lo=0.0, t_hi=6.0)
    assert red["window_s"] == pytest.approx(6.0)
    assert red["busy_s_per_chip"] == pytest.approx([3.0, 1.0])
    assert red["busy_s"] == pytest.approx(2.0)
    # self time, averaged over the two chips
    assert red["ops"]["while.1"] == pytest.approx((2.0 - 0.5 - 0.75) / 2)
    assert red["ops"]["fusion.1"] == pytest.approx(0.25)
    assert red["ops"]["flash_fwd.3"] == pytest.approx(0.5)
    assert red["op_calls"]["fusion.9"] == 1
    # every op second is in exactly one op's self time
    assert sum(red["ops"].values()) == pytest.approx(red["busy_s"])
    gaps = red["idle_gaps"]
    # chip 0: [0,1) mid 0.5 → pack (inner ended at 0.4); [3,4) mid 3.5 →
    # dispatch; [5,6] mid 5.5 → nothing of ours. chip 1: [0,0.5) mid 0.25
    # → inner (innermost); [1.5,6] mid 3.75 → dispatch.
    assert gaps["bench/pack"] == pytest.approx(1.0 / 2)
    assert gaps["bench/inner"] == pytest.approx(0.5 / 2)
    assert gaps["bench/dispatch"] == pytest.approx((1.0 + 4.5) / 2)
    assert gaps["unattributed"] == pytest.approx(1.0 / 2)
    assert sum(gaps.values()) == pytest.approx(6.0 - red["busy_s"])


def test_default_window_is_first_to_last_device_event():
    red = tr.reduce_planes(planes())
    assert red["window_s"] == pytest.approx(4.5)  # 0.5 .. 5.0


def test_no_device_plane_reads_as_nothing():
    assert tr.reduce_planes([p for p in planes()
                             if not p["name"].startswith("/device")]) == {}
    assert tr.reduce_trace(os.path.join(HERE, "no-such-dir")) == {}


def test_top_merges_instances_of_one_kind():
    assert tr.top({"fusion.1": 1.0, "fusion.22": 2.0, "copy": 0.5}, 2) == [
        ["fusion", 3.0], ["copy", 0.5]]
    assert tr.base_name("custom-call.12 f32[8]") == "custom-call"
    text = ("%flash_attention.6 = bf16[1,14,3840,128]{3,2,1,0:T(8,128)(2,1)} "
            "custom-call(bf16[1,14,3840,128]{3,2,1,0} %copy-done.19)")
    assert tr.op_key(text) == "flash_attention.6 bf16[1,14,3840,128]"
    assert tr.op_key("%while.7 = (s32[]{:T(128)}, bf16[1,3840,896]{1,2,0}) "
                     "while(...)") == "while.7 s32[]"
    assert tr.base_name("block_k_384.9 bf16[1]") == "block_k_384"


def test_recorded_chip_trace():
    path = os.path.join(HERE, "data", "small.xplane.pb")
    if not os.path.isfile(path):
        pytest.skip("no recorded trace beside the test")
    import json

    with open(os.path.join(HERE, "data", "small.expected.json")) as f:
        want = json.load(f)
    red = tr.reduce_planes(tr.read_xplane(path))
    assert red["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert red["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert len(red["busy_s_per_chip"]) == want["chips"]
    assert sum(red["ops"].values()) == pytest.approx(red["busy_s"], rel=1e-6)
    assert sum(red["idle_gaps"].values()) == pytest.approx(
        red["window_s"] - red["busy_s"], rel=1e-6)
    for name, secs in want["ops"].items():
        assert red["ops"][name] == pytest.approx(secs, rel=1e-9)
    for name, secs in want["idle_gaps"].items():
        assert red["idle_gaps"][name] == pytest.approx(secs, rel=1e-9)


def test_flash_readers_on_known_ops():
    from benchmark import peaks, readers

    cfg = {"num_attention_heads": 14, "num_key_value_heads": 2,
           "hidden_size": 896}
    fwd = "flash_attention.6 bf16[2,14,1024,128]"
    dkv = "flash_mha_bwd_dkv_block_q_384.9 bf16[2,14,1024,128]"
    dq = "flash_mha_bwd_dq_block_q_384.9 bf16[2,14,1024,128]"
    records = {
        "config": cfg, "device": {"kind": "TPU v5 lite"},
        "trace": {"busy_s": 1.0, "ops": {fwd: 0.03, dkv: 0.04, dq: 0.02,
                                         "fusion.1 f32[8]": 0.5},
                  "op_calls": {fwd: 3, dkv: 1, dq: 1, "fusion.1 f32[8]": 9}},
    }
    assert readers.flash_attn_busy_pct(records) == pytest.approx(9.0)
    t_f = peaks.least_time(*peaks.flash_attention_cost(
        2, 1024, 14, 2, 64, False), "TPU v5 lite")[0]
    t_b = peaks.least_time(*peaks.flash_attention_cost(
        2, 1024, 14, 2, 64, True), "TPU v5 lite")[0]
    assert readers.flash_attn_roofline(records) == pytest.approx(
        100 * (3 * t_f + t_b) / 0.09)
    # forward at these sizes: 2*2*2*14*(1024^2/2)*64 flops
    assert peaks.flash_attention_cost(2, 1024, 14, 2, 64, False)[0] == (
        2 * 2 * 2 * 14 * (1024 * 1024 / 2) * 64)
    assert readers.flash_attn_roofline({"trace": {}}) is None
    with pytest.raises(KeyError):
        peaks.peak("TPU v9")
