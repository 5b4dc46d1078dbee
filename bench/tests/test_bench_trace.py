"""The trace reduction on a small synthetic trace (CPU; no TPU topology)."""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
from bench import trace  # noqa: E402


def _event(meta: int, start_ns: int, dur_ns: int) -> str:
    return f"events {{ metadata_id: {meta} offset_ps: {start_ns * 1000} duration_ps: {dur_ns * 1000} }}"


def _plane(pid: int, name: str, line: str, events: list[str], names: list[str]) -> str:
    quoted = [n.replace('"', '\\"') for n in names]
    meta = "\n".join(f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
                     for i, n in enumerate(quoted, 1))
    return (f'planes {{ id: {pid} name: "{name}" lines {{ id: {pid} name: "{line}" '
            f'timestamp_ns: 0 {" ".join(events)} }} {meta} }}')


FUSION = "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop"
KERNEL = ('%closed_call.2 = bf16[1,4,256,128]{3,2,1,0} custom-call(bf16[1,4,256,128]{3,2,1,0} %q), '
          'custom_call_target="tpu_custom_call"')


def _profile(window=(0, 1000)):
    from jax.profiler import ProfileData

    host = _plane(1, "/host:CPU", "python3", [
        _event(1, window[0], window[1] - window[0]),   # window
        _event(2, 100, 300),                           # train_step 100..400
        _event(3, 600, 200),                           # request_resize 600..800
    ], ["window", "train_step", "request_resize"])
    dev0 = _plane(2, "/device:TPU:0", "XLA Ops", [
        _event(3, 100, 300),    # a while loop 100..400 holding the next two
        _event(1, 120, 80),     # fusion.1   120..200
        _event(2, 250, 100),    # the kernel 250..350
        _event(1, 500, 50),     # fusion.1   500..550
        _event(1, 950, 100),    # fusion.1   950..1050, cut at the window's end
    ], [FUSION, KERNEL, "%while.3 = (s32[]) while((s32[]) %t), body=%b"])
    dev1 = _plane(3, "/device:TPU:1", "XLA Ops", [_event(1, 0, 1000)], ["all-reduce"])
    other = _plane(4, "/device:TPU:0 SparseCore 0", "XLA Ops", [_event(1, 0, 1000)], ["x"])
    return ProfileData.from_text_proto("\n".join([host, dev0, dev1, other]))


def test_busy_ops_and_gaps():
    s = trace.summarize(_profile())
    assert s.window_s == pytest.approx(1e-6)
    assert sorted(s.devices) == [0, 1]
    d0 = s.devices[0]
    # union: 100..400, 500..550, 950..1000
    assert d0.busy_s == pytest.approx(400e-9)
    assert s.devices[1].busy_s == pytest.approx(1000e-9)
    # self times: the loop less what it holds
    assert s.op_time(0, "tpu_custom_call", "= bf16[1,4,256,128]") == (pytest.approx(100e-9), 1)
    assert s.op_time(0, "fusion(") == (pytest.approx(180e-9), 3)
    assert s.op_time(0, "while(") == (pytest.approx(120e-9), 1)
    # gaps: 550..950 (mid 750: request_resize), 0..100 and 400..500 (window only)
    assert [label for label, _ in d0.gaps] == ["request_resize", "window", "window"]
    assert [g for _, g in d0.gaps] == pytest.approx([400e-9, 100e-9, 100e-9])
    b = s.breakdown(0)
    assert b["device_ops"] == [["fusion.1 fusion", pytest.approx(180e-9)],
                               ["while.3 while", pytest.approx(120e-9)],
                               ["closed_call.2 custom-call", pytest.approx(100e-9)]]
    assert len(b["idle_gaps"]) == 3


def test_a_trace_without_the_window_span_is_refused():
    from jax.profiler import ProfileData

    dev = _plane(2, "/device:TPU:0", "XLA Ops", [_event(1, 0, 10)], ["fusion"])
    with pytest.raises(ValueError, match="window"):
        trace.summarize(ProfileData.from_text_proto(dev))
