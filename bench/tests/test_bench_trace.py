"""Trace reduction on a small synthetic trace: busy/idle union, program
time, idle attribution to host spans, and the top-ops breakdown."""
import bench_fixture  # noqa: F401  (puts the repo on sys.path)
import pytest

from bench.lib import trace as T


def _trace():
    dev = T.DeviceTrace("/device:TPU:0")
    # two programs: a segment [1.0, 1.4] and a chunk [1.5, 2.0]
    dev.modules = [("jit__segment(11)", 1.0, 1.4), ("jit__chunk(22)", 1.5, 2.0),
                   ("jit__segment(11)", 2.6, 2.9)]
    dev.ops = [("%while.1 = (...) while(...)", 1.0, 1.4),
               ("%fusion.3 = bf16[8] fusion(x)", 1.0, 1.2),
               ("%fusion.4 = bf16[8] fusion(y)", 1.25, 1.4),
               ("%fusion.3 = bf16[8] fusion(x)", 1.5, 2.0),
               ("%copy.1 = bf16[8] copy(z)", 2.6, 2.9)]
    spans = [("bench.window", 0.5, 3.0), ("bench.runtime_step", 0.9, 2.1),
             ("bench.segment", 0.95, 1.45), ("bench.chunk", 1.45, 2.05),
             ("bench.client", 2.1, 2.5)]
    return T.Trace(devices=[dev], host_spans=spans, window=(0.5, 3.0))


def test_union_busy_and_gaps():
    tr = _trace()
    ops = [(s, e) for _, s, e in tr.devices[0].ops]
    # union: [1.0,1.4] + [1.5,2.0] + [2.6,2.9] = 1.2 s
    assert T.busy_s(ops, tr.window) == pytest.approx(1.2)
    assert T.device_busy(tr) == pytest.approx(1.2)
    gaps = T.idle_gaps(ops, tr.window)
    assert gaps.reshape(-1).tolist() == pytest.approx(
        [0.5, 1.0, 1.4, 1.5, 2.0, 2.6, 2.9, 3.0])
    # clipping to a window that cuts an op in half
    assert T.busy_s(ops, (1.2, 1.7)) == pytest.approx(0.2 + 0.2)


def test_program_time_by_name():
    tr = _trace()
    sec, n = T.program_time(tr.devices[0], tr.window, ["jit__segment"])
    assert (sec, n) == (pytest.approx(0.7), 2)
    sec, n = T.program_time(tr.devices[0], (0.0, 1.2), ["jit__segment"])
    assert (sec, n) == (pytest.approx(0.2), 1)
    assert T.program_name("jit_audio_pipeline_batch(140926579179067119)") \
        == "jit_audio_pipeline_batch"


def test_idle_gaps_go_to_innermost_host_span():
    tr = _trace()
    gaps = T.idle_gaps([(s, e) for _, s, e in tr.devices[0].ops], tr.window)
    got = dict((k, v) for k, v in T.attribute(gaps, tr.host_spans))
    # [0.5,1.0]: mid 0.75 -> only the window span; [1.4,1.5]: mid 1.45 is
    # inside segment (ends 1.45) and chunk (starts 1.45): the later start
    # (chunk) is innermost; [2.0,2.6]: mid 2.3 in client; [2.9,3.0] window
    assert got["bench.window"] == pytest.approx(0.6)
    assert got["bench.chunk"] == pytest.approx(0.1)
    assert got["bench.client"] == pytest.approx(0.6)


def test_top_ops_skips_control_flow_parents():
    tr = _trace()
    top = dict((k, v) for k, v in T.top_ops(tr.devices[0], tr.window))
    assert "jit__segment/while.1" not in top
    assert top["jit__segment/fusion.3"] == pytest.approx(0.2)
    assert top["jit__chunk/fusion.3"] == pytest.approx(0.5)
    assert top["jit__segment/copy.1"] == pytest.approx(0.3)
    assert sum(top.values()) == pytest.approx(1.15)
