"""The span helper (repro.utils.spans): timing, nesting, ids, the bounded
ring, and the profiler's host plane."""

import glob
import threading
import time

import jax
import pytest

from repro.utils import spans
from repro.utils.spans import span


def _mine(name_prefix: str) -> list[tuple]:
    return [r for r in spans.recent() if r[0].startswith(name_prefix)]


def test_a_span_times_itself_and_keeps_its_ids():
    with span("test/outer", step=7) as outer:
        time.sleep(0.01)
        with span("test/inner", step=7, gen=2) as inner:
            time.sleep(0.005)
    assert outer.seconds >= 0.015 and inner.seconds >= 0.005
    assert outer.t0 <= inner.t0 <= inner.t1 <= outer.t1
    recs = _mine("test/")[-2:]
    # the inner span closes first
    assert [r[0] for r in recs] == ["test/inner", "test/outer"]
    assert recs[0][3] == {"step": 7, "gen": 2} and recs[1][3] == {"step": 7}
    assert recs[1][1:3] == (outer.t0, outer.t1)
    assert recs[0][4] == recs[1][4] == threading.get_ident()


def test_spans_of_two_threads_carry_their_thread():
    def work():
        with span("test/thread"):
            pass

    th = threading.Thread(target=work)
    th.start()
    th.join()
    with span("test/thread"):
        pass
    a, b = _mine("test/thread")[-2:]
    assert a[4] == th.ident and b[4] == threading.get_ident() and a[4] != b[4]


def test_a_failing_block_still_closes_its_span():
    with pytest.raises(ValueError):
        with span("test/raises") as s:
            raise ValueError("x")
    assert s.seconds >= 0 and _mine("test/raises")[-1][1:3] == (s.t0, s.t1)


def test_the_ring_is_bounded():
    for i in range(spans.RING_SIZE + 10):
        with span("test/ring", step=i):
            pass
    recs = spans.recent()
    assert len(recs) == spans.RING_SIZE
    assert recs[-1][3] == {"step": spans.RING_SIZE + 9}
    assert recs[0][3] == {"step": 10}


def test_a_span_lands_on_the_profilers_host_plane(tmp_path):
    from jax.profiler import ProfileData

    x = jax.numpy.ones(8)
    jax.profiler.start_trace(str(tmp_path))
    with span("test/traced", step=3):
        (x + 1).block_until_ready()
    jax.profiler.stop_trace()
    path = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)[0]
    found = [
        (ev.name, dict(ev.stats))
        for plane in ProfileData.from_file(path).planes
        if plane.name.startswith("/host:")
        for line in plane.lines
        for ev in line.events
        if ev.name == "test/traced"
    ]
    assert found == [("test/traced", {"step": 3})]


def test_a_slow_step_logs_its_phase_split(caplog):
    from types import SimpleNamespace

    from repro.core.controller import SLOW_STEP_FACTOR, LiveRController

    def step(n, seconds):
        s = span("liver/step", step=n)
        s.t0, s.t1 = 0.0, seconds
        return s

    def phase(name, seconds):
        s = span(name)
        s.t0, s.t1 = 0.0, seconds
        return s

    ctrl = SimpleNamespace(iteration_times=[0.1] * 40)
    split = (phase("liver/batch", 0.001), phase("liver/wait", 1.5))
    with caplog.at_level("WARNING", logger="repro.core.controller"):
        LiveRController._report_slow_step(ctrl, step(40, 0.1 * SLOW_STEP_FACTOR), split)
        assert not caplog.records
        ctrl.iteration_times.append(1.6)
        LiveRController._report_slow_step(ctrl, step(41, 1.6), split)
    (rec,) = caplog.records
    assert "step 41 took 1600.0 ms against a median of 100.0 ms" in rec.getMessage()
    assert "liver/batch 1.0, liver/wait 1500.0" in rec.getMessage()


def test_records_and_world_timings_are_their_spans(subproc):
    out = subproc(
        """
        from repro.configs import get_config
        from repro.configs.base import ParallelConfig
        from repro.core.controller import LiveRController
        from repro.optim import AdamWConfig
        from repro.utils import spans

        def of(name, **ids):
            return [r for r in spans.recent()
                    if r[0] == name and all(r[3].get(k) == v for k, v in ids.items())]

        def secs(r):
            return r[2] - r[1]

        cfg = get_config("qwen3-1.7b").reduced()
        ctrl = LiveRController(cfg, ParallelConfig(dp=1, tp=2), AdamWConfig(),
                               seq_len=16, global_batch=4, overlap="stream",
                               stream_k=1, sync_compile=True)
        ctrl.train_steps(2)
        (step,) = of("liver/step", step=1)
        kids = [r for r in spans.recent() if r[4] == step[4]
                and step[1] <= r[1] and r[2] <= step[2] and r[0] != "liver/step"]
        assert [r[0] for r in kids] == ["liver/batch", "liver/dispatch",
                                        "liver/wait", "liver/boundary"], kids
        assert all(r[3] == {"step": 1} for r in kids)
        assert ctrl.world.timings["compile_s"] == secs(of("liver/world/compile")[-1])

        # streamed grow: stream rounds, then the split-step commit
        ctrl.request_resize(ParallelConfig(dp=1, tp=4))
        ctrl.wait_shadow_ready()
        while not ctrl.records:
            ctrl.train_steps(1)
        rec = ctrl.records[-1]
        g = rec.gen_id
        assert rec.prepare_s == secs(of("liver/prepare", gen=g)[-1])
        assert rec.total_pause_s == secs(of("liver/commit", gen=g)[-1])
        for f in ("transfer", "update", "switch"):
            assert getattr(rec, f + "_s") == secs(of("liver/commit/" + f, gen=g)[-1]), f
        # drain: the grad tail before the pause and the re-sync tail inside it
        (tail,) = of("liver/commit/grad_tail", gen=g)
        (drain,) = of("liver/commit/drain", gen=g)
        (pause,) = of("liver/commit", gen=g)
        assert rec.drain_s == secs(tail) + secs(drain)
        assert tail[2] <= pause[1] <= drain[1]
        rounds = of("liver/stream_round", gen=g)
        assert rounds and len(of("liver/stream/dispatch", gen=g)) == len(rounds)
        assert len(of("liver/resync/drain", gen=g)) == 1

        # stop-copy shrink: drain, transfer and switch inside the pause
        ctrl.request_resize(ParallelConfig(dp=1, tp=2), overlap="stop_copy")
        ctrl.wait_shadow_ready()
        n = len(ctrl.records)
        while len(ctrl.records) == n:
            ctrl.train_steps(1)
        rec = ctrl.records[-1]
        g = rec.gen_id
        assert rec.total_pause_s == secs(of("liver/commit", gen=g)[-1])
        for f in ("drain", "transfer", "switch"):
            assert getattr(rec, f + "_s") == secs(of("liver/commit/" + f, gen=g)[-1]), f
        print("SPANS_OK")
        """,
        n_devices=4,
    )
    assert "SPANS_OK" in out
