"""The readers of ``repro.sim``'s spans and scopes (``sim_spans.py`` and
the six ``layer_metrics`` that use it), on hand-made traces and results
whose answers are known, and the op-to-scope map on a real runner."""
from __future__ import annotations

from collections import namedtuple
from pathlib import Path
from types import SimpleNamespace

import pytest

import harness
import sim_spans

Span = namedtuple("Span", "name parent start_ns end_ns")
ROOT = "sim.simulate_batch"
LOOP = "jit(run)/sim_cycle_loop/while"
SCOPES = {
    "fusion.1": LOOP + "/body/closed_call/execute/presence/jit(_take)/gather",
    "fusion.2": LOOP + "/body/closed_call/commit/scatter",
    "while.1": LOOP,
}
NEW = ("verify_upload_ms", "verify_pullback_ms", "verify_check_ms",
       "cycle_loop_execute_ms", "cycle_loop_commit_ms",
       "verify_idle_unspanned_ms")


@pytest.fixture(scope="module")
def tracemod():
    return harness.load_module(harness.HERE / "trace.py")


def _result(t0, phases):
    """A call's spans: the root ``[0, 300)`` or ``[0, 400)`` and its
    phases, each ``(name, start, end)`` after ``t0`` on the host clock."""
    end = max(e for _, _, e in phases) + 10
    return SimpleNamespace(spans=[Span(ROOT, None, t0, t0 + end)] + [
        Span(n, ROOT, t0 + s, t0 + e) for n, s, e in phases])


def _case(tracemod):
    """Two calls inside harness spans ``[100, 400)`` and ``[500, 900)``.

    Call A: an upload program (120-140), then ``run`` (150-300) of an
    ``execute`` op (100 ns) and a ``commit`` op (50 ns); its spans cover
    110-380 once shifted.  Call B: ``run`` (560-800) is a ``while`` op
    holding an ``execute`` op (130 ns), a ``commit`` op (80 ns) and an op
    no scope names (10 ns), 20 ns of its own; B has no upload span, so
    its idle start (500-550) is covered by none."""
    T = tracemod
    dev = T.Device(
        modules=[("convert_element_type", 120, 140), ("run", 150, 300),
                 ("run", 560, 800)],
        ops=[("convert.1", 120, 140), ("fusion.1", 150, 250),
             ("fusion.2", 250, 300), ("while.1", 560, 800),
             ("fusion.1", 570, 700), ("fusion.2", 700, 780),
             ("copy.3", 790, 800)])
    tr = T.Trace(devices=[dev], window=(0, 1000), spans=[
        T.Span("simulate_batch", 100, 400),
        T.Span("simulate_batch", 500, 900)])
    a = _result(7 * 10**18, [("sim.upload", 10, 60),
                             ("sim.cycle_loop", 60, 210),
                             ("sim.pullback", 210, 240),
                             ("sim.check", 240, 280)])
    a.spans[0] = a.spans[0]._replace(end_ns=a.spans[0].start_ns + 300)
    b = _result(7 * 10**18 + 10**9, [("sim.cycle_loop", 50, 320),
                                     ("sim.pullback", 320, 350),
                                     ("sim.check", 350, 390)])
    return tr, SimpleNamespace(results=[a, b])


def _read(name, tr, run):
    return harness.reader(name).read(tr, run, SimpleNamespace(peaks={}))


@pytest.fixture
def scopes(monkeypatch):
    monkeypatch.setattr(sim_spans, "op_scopes", lambda run: SCOPES)


def test_span_readers_average_per_call(tracemod):
    tr, run = _case(tracemod)
    assert _read("verify_upload_ms", tr, run) == pytest.approx(25e-6)
    assert _read("verify_pullback_ms", tr, run) == pytest.approx(30e-6)
    assert _read("verify_check_ms", tr, run) == pytest.approx(40e-6)


def test_idle_unspanned_counts_the_uncovered_gaps(tracemod):
    tr, run = _case(tracemod)
    # A: 100-110 and 380-400; B: 500-550 (no upload span) and 890-900
    assert _read("verify_idle_unspanned_ms", tr, run) == pytest.approx(
        (30 + 60) / 2 / 1e6)


def test_phase_readers_split_the_loop_by_scope(tracemod, scopes):
    tr, run = _case(tracemod)
    assert _read("cycle_loop_execute_ms", tr, run) == pytest.approx(
        (100 + 130) / 2 / 1e6)
    assert _read("cycle_loop_commit_ms", tr, run) == pytest.approx(
        (50 + 80) / 2 / 1e6)
    split = sim_spans.phase_ms(tr, run)
    assert split == pytest.approx({
        "execute/presence": 115e-6, "commit": 65e-6,
        "sim_cycle_loop": 10e-6,   # the while op's own time
        "none": 5e-6})             # the op no scope names, not dropped
    # the upload program's op is outside ``run`` and counts nowhere
    assert sum(split.values()) == pytest.approx(
        tr.module_seconds("run")[0] * 1e3 / 2)


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_spans_reads_nothing(tracemod, scopes, name):
    tr, _ = _case(tracemod)
    parent = SimpleNamespace(results=[SimpleNamespace(), SimpleNamespace()])
    assert _read(name, tr, parent) is None
    assert _read(name, tr, SimpleNamespace(results=[])) is None


def test_unequal_call_counts_read_nothing(tracemod):
    tr, run = _case(tracemod)
    run.results.append(run.results[0])
    assert _read("verify_idle_unspanned_ms", tr, run) is None


@pytest.mark.parametrize("op_name,label", [
    (LOOP + "/body/closed_call/execute/presence/reduce_or",
     "execute/presence"),
    (LOOP + "/body/closed_call/execute/operand_read/jit(_take)/gather",
     "execute/operand_read"),
    (LOOP + "/body/closed_call/execute/alu/jit(_where)/select_n",
     "execute/alu"),
    (LOOP + "/body/closed_call/execute/value_write/scatter",
     "execute/value_write"),
    (LOOP + "/body/closed_call/execute/jit(clip)/min", "execute"),
    (LOOP + "/body/closed_call/commit/jit(_take)/gather", "commit"),
    (LOOP + "/body/add", "sim_cycle_loop"),
    ("jit(run)/mul", "none"),
])
def test_scope_of(op_name, label):
    assert sim_spans.scope_of(op_name) == label


def test_op_scopes_of_a_real_runner():
    """The map read from the compiled text of the cell's runner names
    every phase of the loop (two stored mappings, on the CPU)."""
    from repro.compiler.artifact import CompileResult
    from repro.sim.batch import prepare_batch

    data = Path(harness.HERE) / "data" / "table2"
    ms = [m for f in ("atax_u2__plaid.json", "jacobi_u1__st.json")
          for m in CompileResult.load(str(data / f)).rebuild_mappings()]
    run = SimpleNamespace(prepared=prepare_batch(ms, iterations=3),
                          backend="jnp")
    labels = {sim_spans.scope_of(n) for n in sim_spans.op_scopes(run)
              .values()}
    assert {"execute/operand_read", "execute/presence", "execute/alu",
            "execute/value_write", "commit"} <= labels
