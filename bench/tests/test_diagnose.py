"""The diagnostic run (bench/diagnose.py): idle gaps charged to host events
of any name on hand-made intervals, and the whole report at a tiny size on
the CPU, where XLA's threads stand for the device."""

import functools
import json

import pytest

from bench import diagnose as D
from bench import run as harness
from bench import scopes as S
from bench import trace as T
from bench.tests.test_harness import ENTRY, TINY


def cpu_op_line(plane, line):
    return plane == "/host:CPU" and line.startswith(("tf_XLAPjRtCpuClient",
                                                     "tf_XLAEigen"))


def instructions_only(trace):
    """Drop the CPU runtime's own events from XLA's threads (the thunk
    executor's, the thread pool's, the ``end:`` marks), so that the lines
    hold operations as a chip's device line does."""
    trace.ops = {d: [o for o in ops if "::" not in o[0] and not o[0].startswith("end: ")]
                 for d, ops in trace.ops.items()}


def test_idle_gaps_go_to_the_innermost_host_event():
    tr = T.Trace(ops={"/device:TPU:0": [("a", 10, 20), ("b", 40, 50), ("c", 80, 90)]},
                 spans=[(T.WINDOW, 0, 100)])
    events = [("decode", 0, 60),                 # open over the first two gaps
              ("token_fetch", 15, 60),
              ("_value", 22, 38),                # the wait, inside the fetch
              ("TransferFromDevice", 52, 58),    # the copy, on another thread
              ("Python", 62, 78),
              ("long before", -50, 5)]
    got = dict(D.idle_causes(tr, events))
    # gaps 0-10 (mid 5: decode, opened after "long before"), 20-40 (mid
    # 30: _value), 50-80 (mid 65: Python), 90-100 (mid 95: none open)
    assert got == pytest.approx({"decode": 10e-9, "_value": 20e-9,
                                 "Python": 30e-9, "(none)": 10e-9})
    assert sum(got.values()) == pytest.approx(tr.window_s() - tr.busy_s())


def test_idle_gap_inside_a_long_event_started_early():
    """An event that opened long before, with many closed ones after it,
    still takes the gap at its midpoint."""
    tr = T.Trace(ops={"/device:TPU:0": [("a", 0, 10), ("b", 990, 1000)]},
                 spans=[(T.WINDOW, 0, 1000)])
    events = [("outer", 1, 999)] + [(f"short{i}", 20 + i, 21 + i) for i in range(400)]
    assert D.idle_causes(tr, events) == [["outer", pytest.approx(980e-9)]]


@pytest.fixture
def cpu_run(monkeypatch):
    """A traced tiny serving run on the CPU through the diagnostic's hooks."""
    import repro.launch.compile_cache as cc

    monkeypatch.setattr(cc, "enable_compile_cache", lambda: None)
    monkeypatch.setattr(T, "read", functools.partial(T.read, device_line=cpu_op_line))
    # At this size the greedy pick, another program, is a few per cent of
    # a step, where on the chip it is ~0.1 %.
    monkeypatch.setattr(S, "OTHER_MAX", 0.1)
    entry = ENTRY["glm4-decode-b32"]
    traffic = dict(harness.load("traffic", entry["traffic"]), batch=2,
                   prompt=16, new_tokens=8, max_seq=32)
    config = dict(TINY, arch="glm4-9b", tie_word_embeddings=False)
    cc.log_compiles()
    real = T.read
    with D.keeping_host_events([], device_line=cpu_op_line) as events:
        rec = harness.run_cell(entry, 2**31 + 13, 0.5, True,
                               files={"config": config, "traffic": traffic})
    assert T.read is real                        # put back on the way out
    instructions_only(rec.trace)
    return rec, events, cc.compiles()


def test_report_at_a_tiny_size(cpu_run):
    rec, events, compiled = cpu_run
    assert events
    lines = D.report(rec, events, compiled)
    text = "\n".join(lines)
    for span in S.SPANS:
        assert f"scopes {span}: " in text and f"scopes {span}: none" not in text
    assert ":cache" in text and "attn_core" in text
    assert any(line.startswith("idle: ") for line in lines)
    assert any(line.startswith("compiles: ") for line in lines)
    idle = sum(s for _, s in D.idle_causes(rec.trace, events, top=10**6))
    assert idle == pytest.approx(rec.trace.window_s() - rec.trace.busy_s(), rel=1e-6)


def test_traced_line_carries_the_scopes(cpu_run, monkeypatch):
    """The traced result line gives the device seconds by scope next to
    the top operations and idle gaps, and the checks stay its last key."""
    import bench.peaks

    rec, _, _ = cpu_run
    monkeypatch.setattr(bench.peaks, "peaks", lambda kind: {
        "bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    line = harness.result(bench, "glm4-decode-b32", rec, True)
    got = json.loads(json.dumps(line))["breakdown"]
    assert set(got) == {"device_ops", "idle_gaps", "scopes"}
    assert got["scopes"] == S.of(rec) and got["scopes"]["decode"]
    assert list(line)[-1] == "checks"
