"""The trace reducer and the per-layer readers, on a trace recorded on a
TPU v5e and on hand-made traces whose answers are known."""
from __future__ import annotations

from pathlib import Path
from types import SimpleNamespace

import pytest

import harness

FIXTURE = Path(__file__).parent / "fixtures" / "small_trace.xplane.pb"


@pytest.fixture(scope="module")
def tracemod():
    return harness.load_module(harness.HERE / "trace.py")


@pytest.fixture(scope="module")
def recorded(tracemod):
    """Three runs of a jitted ``run`` (a 20-trip gather loop, about 0.6 s
    each) and of a small ``serve_step``, with host spans ``cycle_loop``,
    ``pull_check`` (a 10 ms sleep) and ``generate`` around them."""
    return tracemod.load(str(FIXTURE),
                         ["cycle_loop", "pull_check", "generate"])


def test_recorded_modules_and_busy(recorded):
    secs, n = recorded.module_seconds("run")
    assert n == 3 and 1.80 < secs < 1.81
    _, n = recorded.module_seconds("serve_step")
    assert n == 3
    assert 0 < recorded.busy_s < recorded.window_s
    assert 1.80 < recorded.busy_s < 1.81
    assert [sp.name for sp in recorded.spans].count("cycle_loop") == 3


def test_recorded_top_ops_are_self_time(recorded):
    ops = recorded.top_ops(5)
    names = [n for n, _ in ops]
    assert "while.1" not in names[:1], ops        # the loop holds its body
    assert ops[0][1] > 1.7
    assert all(a[1] >= b[1] for a, b in zip(ops, ops[1:]))


def test_recorded_gaps_cover_the_idle_time(recorded):
    gaps = recorded.gaps()
    idle = sum(e - s for _, s, e in gaps) / 1e9
    assert idle == pytest.approx(recorded.window_s - recorded.busy_s,
                                 abs=1e-9)
    labels = dict(recorded.idle_by_label(10))
    assert any(k.startswith("pull_check:") for k in labels)
    assert max(labels.values()) > 0.01            # the 10 ms sleeps


def _synthetic(tracemod):
    """Two calls of program ``run`` (100 and 200 ns of device time) inside
    spans ``simulate_batch`` of 150 and 260 ns; ops cover the programs."""
    T = tracemod
    dev = T.Device(modules=[("run", 20, 120), ("run", 230, 430)],
                   ops=[("fusion.1", 20, 120), ("gather.2", 230, 330),
                        ("fusion.1", 330, 430)])
    spans = [T.Span("simulate_batch", 0, 150), T.Span("simulate_batch",
                                                        200, 460)]
    return T.Trace(devices=[dev], spans=spans, window=(0, 500))


def test_synthetic_gaps_and_labels(tracemod):
    tr = _synthetic(tracemod)
    assert tr.busy_s == pytest.approx(300e-9)
    assert tr.window_s == pytest.approx(500e-9)
    labels = dict(tr.idle_by_label())
    assert labels["simulate_batch:start->run"] == pytest.approx(50e-9)
    assert labels["simulate_batch:run->end"] == pytest.approx(60e-9)
    assert labels["none:run->run"] == pytest.approx(50e-9)
    assert dict(tr.top_ops())["fusion.1"] == pytest.approx(200e-9)


def test_readers_on_synthetic_trace(tracemod):
    tr = _synthetic(tracemod)
    run = SimpleNamespace(results=[None, None], call_bytes=lambda: 819)
    ctx = SimpleNamespace(peaks={"hbm_bytes_per_s": 819e9,
                                 "bf16_flops_per_s": 197e12})
    read = lambda name: harness.reader(name).read(tr, run, ctx)
    assert read("cycle_loop_ms") == pytest.approx(150e-6)
    # spans 150 and 260 ns less 100 and 200 ns of loop: 55 ns a call
    assert read("verify_host_ms") == pytest.approx(55e-6)
    # 2 * 819 bytes at 819 GB/s is 2 ns, over 300 ns of loop
    assert read("cycle_loop_roofline") == pytest.approx(100 * 2 / 300)
    assert read("device_idle.verify") == pytest.approx(40.0)


def test_serve_readers_count_whole_calls(tracemod):
    T = tracemod
    mods = [("prefill_step", 0, 100)] + [
        ("serve_step", 100 + 10 * i, 108 + 10 * i) for i in range(3)]
    tr = T.Trace(devices=[T.Device(modules=mods, ops=[
        ("op", s, e) for _, s, e in mods])], spans=[], window=(0, 200))
    work = {"prefill_flops": 197 * 50, "decode_steps": 3,
            "decode_flops": 197 * 12, "decode_bytes": 819 * 6}
    run = SimpleNamespace(batches=[{}], work=lambda: work)
    ctx = SimpleNamespace(peaks={"hbm_bytes_per_s": 819e9,
                                 "bf16_flops_per_s": 197e12})
    read = lambda name: harness.reader(name).read(tr, run, ctx)
    assert read("prefill_mfu") == pytest.approx(50.0 * 1e-3)
    assert read("decode_mfu") == pytest.approx(100 * 12e-3 / 24)
    assert read("decode_roofline") == pytest.approx(100 * 6 / 24)
    # a traced window that holds part of a call reads nothing
    run2 = SimpleNamespace(batches=[{}, {}], work=lambda: work)
    assert harness.reader("decode_mfu").read(tr, run2, ctx) is None
