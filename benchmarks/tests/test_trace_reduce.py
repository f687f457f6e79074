"""The reduction from a trace to busy time, op table and named idle gaps."""

import json
import os

import pytest

import trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _trace(ops, modules=(), host=()):
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [list(e) for e in ops]},
            {"name": "XLA Modules", "events": [list(e) for e in modules]},
            {"name": "Steps", "events": [["ignored", 0.0, 1e9]]},
        ]},
        {"name": "/host:CPU", "lines": [{"name": "main/1", "events": [list(e) for e in host]}]},
        {"name": "Task Environment", "lines": []},
    ]}


def test_hand_made_trace():
    ops = [
        ("%copy.5 = bf16[1024,16,8,128]{3,2,1,0} copy(bf16[1024,16,8,128]{3,2,1,0} %p)", 0, 400_000),
        ("%fusion.7 = bf16[4,32,128]{2,1,0} fusion(%a, %b)", 300_000, 300_000),  # overlaps the copy
        ("%copy.9 = bf16[1024,16,8,128]{3,2,1,0} copy(%q)", 1_000_000, 200_000),
        ("%fusion.8 = bf16[4,32,128]{2,1,0} fusion(%a, %b)", 1_210_000, 90_000),  # 10 us after: a short gap
    ]
    modules = [("jit_verify_step_ragged(123)", 0, 600_000), ("jit_verify_step_ragged(123)", 1_000_000, 300_000)]
    host = [
        ("PjitFunction(verify_step_ragged)", 500_000, 600_000),  # covers all of the long gap
        ("np.asarray(jax.Array)", 650_000, 200_000),  # inside it, shorter: loses on overlap
        ("unrelated", 5_000_000, 10),
    ]
    r = trace_reduce.reduce(_trace(ops, modules, host))
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(1.3e-3)
    assert r["busy_s"] == pytest.approx((600_000 + 200_000 + 90_000) / 1e9)
    assert r["ops"]["copy_bf16_1024_16_8_128"] == [pytest.approx(6e-4), 2]
    assert r["ops"]["fusion_bf16_4_32_128"] == [pytest.approx(3.9e-4), 2]
    assert r["modules"]["jit_verify_step_ragged"] == [pytest.approx(9e-4), 2]
    assert r["idle_gaps"] == {
        "PjitFunction": pytest.approx(4e-4), trace_reduce.SHORT_GAPS: pytest.approx(1e-5),
    }
    assert trace_reduce.top(r["ops"], 1) == [["copy_bf16_1024_16_8_128", pytest.approx(6e-4)]]
    assert trace_reduce.matching(r["modules"], "^jit_verify") == (pytest.approx(9e-4), 2)
    assert trace_reduce.matching(r["ops"], "flash") == (0, 0)


def test_gap_with_no_host_event_and_no_device_plane():
    r = trace_reduce.reduce(_trace([("a", 0, 1000), ("b", 101_000, 1000)]))
    assert r["idle_gaps"] == {trace_reduce.NO_HOST_SPAN: pytest.approx(1e-4)}
    with pytest.raises(ValueError, match="no device plane"):
        trace_reduce.reduce({"planes": [{"name": "/host:CPU", "lines": []}]})


@pytest.mark.parametrize("raw,clean", [
    ("%copy.5 = bf16[2048,16,8,128]{3,2,1,0} copy(...)", "copy_bf16_2048_16_8_128"),
    ("%fusion.12 = (bf16[4,32]{1,0}, f32[2]) fusion(...)", "fusion_bf16_4_32"),
    ("jit_prefill(456)", "jit_prefill"),
    ("np.asarray(jax.Array)", "np.asarray"),
    ("tpu::System::Execute=>Done", "tpu::System::Execute_Done"),
])
def test_names_fit_a_ledger_line(raw, clean):
    assert trace_reduce.clean_name(raw) == clean


def test_recorded_slice_of_a_chip_trace():
    """60 ms cut out of a traced run of mistral7b-unshared-chat on a TPU v5
    lite (PR 23). The expected busy time was checked against a count on a
    10 ns grid over the file's own intervals (0.025476 s against 0.025474 s)."""
    path = os.path.join(DATA, "small_trace.json")
    r = trace_reduce.reduce(trace_reduce.load(path))
    with open(os.path.join(DATA, "small_trace.expected.json")) as f:
        want = json.load(f)
    assert r["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert r["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert 0 < r["busy_s"] <= r["window_s"]
    for name, (seconds, count) in want["ops"].items():
        assert r["ops"][name] == [pytest.approx(seconds, rel=1e-9), count]
    assert sum(r["idle_gaps"].values()) == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-6)
    assert set(want["idle_gap_names"]) <= set(r["idle_gaps"])
