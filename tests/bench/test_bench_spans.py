"""The program's spans in a trace, on small synthesized traces: host turns
between decode windows, the two readers built on them, and the device's
idle time split by the innermost span covering it."""
import importlib

import pytest

import bench_cpu as smoke
from bench.lib import harness, spans, trace

PEAK = {"bf16_flops_per_s": 100e12, "hbm_bytes_per_s": 1e12}
READERS = ("host_gap_ms_per_window", "host_gap_ms_max")


def ev(name, start, end):
    return trace.Event(name, start, end)


def window_spans(t, wait, turn):
    """One decode window opened at ``t``: lanes and dispatch, ``wait``
    seconds blocked on the device, then ``turn`` seconds of host work
    (pull, apply, poll, flush) before the next window may open."""
    s = t + 0.002                               # sync wait starts
    e = s + wait                                # device done
    return [ev("engine/decode_window", t, e + turn - 0.0025),
            ev("engine/lanes", t, t + 0.001),
            ev("engine/dispatch", t + 0.001, t + 0.002),
            ev("engine/sync_wait", s, e),
            ev("engine/pull", e, e + 0.001),
            ev("engine/apply", e + 0.001, e + turn - 0.003),
            ev("frontend/poll", e + turn - 0.002, e + turn - 0.001),
            ev("pool/flush_resets", e + turn - 0.001, e + turn)]


TURNS = (0.010, 0.012, 0.020, 0.011, 0.010)


def synthetic_trace(turns=TURNS, window=(0.5, 9.0)):
    """Decode windows of 1.6 s from 0.0, each followed by its host work;
    the device is busy from each dispatch to the end of its sync wait.
    The windows open at 0, 1.612, 3.226, 4.848 and 6.461 s; the traced
    window (0.5, 9.0) cuts the first sync wait (0.002-1.602)."""
    host = [ev("bench.traced_window", *window),
            ev("$<unknown> acquire", 0.0, 10.0)]   # Python tracer's event
    ops, t = [], 0.0
    for turn in turns:
        host += window_spans(t, 1.6, turn)
        ops.append(ev("%fusion.1 = bf16[8] fusion(%a)", t + 0.002,
                      t + 0.002 + 1.6))
        t += 0.002 + 1.6 + turn
    return trace.Trace(window, {"/device:TPU:0": ops}, {}, host)


def run_of(tr):
    return harness.Run(smoke.MODEL, smoke.mix("closed"), None, tr, PEAK)


def test_host_turns_leave_out_waits_across_the_window_edges():
    """A host turn runs from one sync wait's end to the next one's start:
    the host work after a window plus the next window's lanes and
    dispatch (2 ms here)."""
    tr = synthetic_trace()
    assert [e - s for s, e in spans.host_turns(tr)] == [
        pytest.approx(x) for x in (0.014, 0.022, 0.013)]
    tr.window = (0.5, 7.0)                      # now the last wait is cut
    assert [e - s for s, e in spans.host_turns(tr)] == [
        pytest.approx(x) for x in (0.014, 0.022)]


@pytest.mark.parametrize("name,want", [("host_gap_ms_per_window", 49 / 3),
                                       ("host_gap_ms_max", 22.0)])
def test_readers_on_host_turns(name, want):
    mod = importlib.import_module(f"bench.metrics.{name}")
    assert mod.read(run_of(synthetic_trace())) == pytest.approx(want)


@pytest.mark.parametrize("name", READERS)
def test_readers_find_nothing_without_program_spans(name):
    """A program that writes no spans (an older commit) leaves
    the readers nothing to read: None, and no error."""
    mod = importlib.import_module(f"bench.metrics.{name}")
    tr = synthetic_trace()
    tr.host = [e for e in tr.host if not e.name.startswith("engine/")]
    assert mod.read(run_of(tr)) is None
    assert mod.read(run_of(synthetic_trace(turns=(0.01,)))) is None
    assert mod.read(run_of(None)) is None


def test_idle_by_innermost_span():
    """Idle time goes to the innermost span covering it: pull and apply
    inside the window span (whose own 0.5 ms after apply is its self
    time), poll and flush after it, the next window's lanes and dispatch
    before the device starts; time under no program span is ``(none)``,
    the Python tracer's events notwithstanding."""
    tr = synthetic_trace()
    idle = spans.idle_by_span(tr)
    assert sum(idle.values()) == pytest.approx(
        tr.window_s - trace.busy_s(tr))
    tail = 9.0 - (6.461 + 1.602 + 0.010)        # after the last flush
    want = {"engine/pull": 0.005, "engine/apply": 0.043,
            "engine/decode_window": 0.0025, "frontend/poll": 0.005,
            "pool/flush_resets": 0.005, "engine/lanes": 0.004,
            "engine/dispatch": 0.004, spans.NONE: 0.0025 + tail}
    assert idle == {k: pytest.approx(v, abs=1e-9) for k, v in want.items()}
    # the host turns hold all idle time but the tail's and the first gap's
    turns = spans.by_span(tr, spans.host_turns(tr))
    assert sum(turns.values()) == pytest.approx(0.014 + 0.022 + 0.013)
    assert "engine/sync_wait" not in turns
