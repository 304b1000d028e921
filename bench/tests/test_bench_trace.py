"""Trace reduction on a hand-built trace whose answers are known."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from harness import trace  # noqa: E402

MS = 1_000_000_000          # picoseconds in a millisecond


def _event(meta, start_ms, dur_ms):
    return (f"events {{ metadata_id: {meta} offset_ps: {int(start_ms * MS)} "
            f"duration_ps: {int(dur_ms * MS)} }}")


def _line(lid, name, events):
    return (f"lines {{ id: {lid} name: \"{name}\" timestamp_ns: 0 "
            + " ".join(events) + " }")


def _plane(pid, name, lines, names):
    meta = " ".join(f"event_metadata {{ key: {i} value {{ id: {i} "
                    f"name: \"{n}\" }} }}" for i, n in names.items())
    return f"planes {{ id: {pid} name: \"{name}\" " + " ".join(lines) \
        + " " + meta + " }"


# Device (ms from trace start): a prefill program 10-20 holding one
# attention kernel 12-14 and one norm kernel 15-16; a decode program
# 30-60 whose while loop holds attention 31-41 and 45-55 and a norm
# 56-57; one fusion 62-64 outside any program.
# Host: start_trace ends at 5, stop_trace starts at 95, so the window
# is 90 ms; bench.step covers 5-65, bench.wait 65-95.
DEVICE_NAMES = {1: "jit__prefill(1)", 2: "jit__decode_h(2)",
                3: "%flash_e2softmax_paged.3 = f32[1] custom-call()",
                4: "%_qnorm_call.7 = f32[1] custom-call()",
                5: "%while.58 = (s32[]) while()",
                6: "%fusion.12 = f32[1] fusion()"}
HOST_NAMES = {1: "$profiler.py:101 start_trace", 2: "bench.step",
              3: "bench.wait", 4: "$profiler.py:213 stop_trace"}


def _xspace():
    from jax.profiler import ProfileData
    dev = _plane(1, "/device:TPU:0", [
        _line(1, "XLA Modules", [_event(1, 10, 10), _event(2, 30, 30)]),
        _line(2, "XLA Ops", [_event(3, 12, 2), _event(4, 15, 1),
                             _event(5, 30, 30), _event(3, 31, 10),
                             _event(3, 45, 10), _event(4, 56, 1),
                             _event(6, 62, 2)]),
    ], DEVICE_NAMES)
    host = _plane(2, "/host:CPU", [
        _line(1, "python", [_event(1, 0, 5), _event(2, 5, 60),
                            _event(3, 65, 30), _event(4, 95, 5)]),
    ], HOST_NAMES)
    return ProfileData.text_proto_to_serialized_xspace(dev + " " + host)


@pytest.fixture
def reduced(tmp_path):
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(_xspace())
    return trace.reduce(tmp_path, ("bench.step", "bench.wait"))


def test_window_and_busy(reduced):
    assert reduced["window_s"] == pytest.approx(0.090)
    # busy is the union of ops, not of programs: 12-14, 15-16, the while
    # 30-60 and 62-64
    assert reduced["busy_s"] == pytest.approx(0.002 + 0.001 + 0.030 + 0.002)


def test_modules_and_kernels(reduced):
    assert trace.module_time(reduced, "prefill") == pytest.approx((0.010, 1))
    assert trace.module_time(reduced, "decode") == pytest.approx((0.030, 1))
    assert trace.kernel_time(reduced, "attention", "prefill") == \
        pytest.approx(0.002)
    assert trace.kernel_time(reduced, "attention", "decode") == \
        pytest.approx(0.020)
    assert trace.kernel_time(reduced, "norm", None) == pytest.approx(0.002)
    assert trace.module_time(None, "decode") == (0.0, 0)


def test_top_ops_leave_out_containers(reduced):
    top = dict(reduced["top_ops"])
    assert "while" not in top
    assert top["flash_e2softmax_paged"] == pytest.approx(0.022)
    assert top["fusion"] == pytest.approx(0.002)
    assert list(top)[0] == "flash_e2softmax_paged"


def test_idle_gaps_labelled_by_host_span(reduced):
    # gaps in window time (ms): 0-7, 9-10, 11-25, 55-57, 59-90; the last
    # is 1 ms of bench.step and 30 of bench.wait
    gaps = reduced["idle_gaps"]
    assert [g[0] for g in gaps] == ["bench.wait"] + ["bench.step"] * 4
    assert [g[1] for g in gaps] == pytest.approx([0.031, 0.014, 0.007,
                                                  0.002, 0.001])
    assert reduced["idle_by_host_span"]["bench.step"] == \
        pytest.approx(0.024)


def test_union_merges_overlaps():
    assert trace.union([(5, 7), (0, 2), (1, 3), (6, 9)]) == [[0, 3], [5, 9]]
    assert trace.op_base("%flash_e2softmax_paged.21 = f32[] x()") == \
        "flash_e2softmax_paged"


def test_missing_trace_raises(tmp_path):
    with pytest.raises(RuntimeError):
        trace.reduce(tmp_path, ())
