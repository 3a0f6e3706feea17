"""The reduction from a device trace and host spans to numbers: exact
arithmetic on a handful of made-up events, then the same functions on a
small trace recorded on a v5e (``cellbench/trace/recorded_v5e.json``)."""

import json

import pytest

from cellbench_tiny import REPO

from cellbench import readers
from cellbench.trace import reduce as tr

# one device, times in ns: a loop that holds a kernel and a fusion, a
# gap, a collective, a gap, the kernel again
FLASH = "%apex_flash_fwd.1 = bf16[8,64] custom-call(bf16[8,64] %q)"
EVENTS = [
    ["%while.5 = (s32[], bf16[8,64]) while(%tuple)", 100, 500],
    [FLASH, 100, 300],
    ["%fusion.7 = bf16[8,64] fusion(%p)", 400, 200],
    ["%all-reduce.2 = f32[64] all-reduce(%g)", 1000, 200],
    [FLASH, 1500, 100],
]
SPANS = [["serve.decode_step", 0, 900], ["serve.prefill", 1150, 400]]


def test_busy_union_counts_an_overlap_once():
    assert tr.merged(EVENTS) == [(100, 600), (1000, 1200), (1500, 1600)]
    assert tr.busy_ns(EVENTS) == 800


def test_clip_cuts_events_at_the_window():
    assert tr.clip(EVENTS, 400, 1100) == [
        [EVENTS[0][0], 400, 200], [EVENTS[2][0], 400, 200],
        [EVENTS[3][0], 1000, 100]]


def test_a_loop_is_not_counted_on_top_of_its_body():
    timed = {e[0]: e[3] for e in tr.with_self_times(EVENTS[:3])}
    assert timed == {EVENTS[0][0]: 0, FLASH: 300, EVENTS[2][0]: 200}
    # an event that only overlaps another is not its child
    timed = tr.with_self_times([["a", 0, 100], ["b", 50, 100]])
    assert [e[3] for e in timed] == [100, 100]



def test_reduced_window_idle_share_and_kernel_time():
    red = tr.Reduced({"/device:TPU:0": EVENTS}, 0, 2000, SPANS)
    assert red.window_s == pytest.approx(2e-6)
    assert red.busy_s == pytest.approx(0.8e-6)
    assert red.idle_share == pytest.approx(0.6)
    assert red.seconds("apex_flash_fwd") == pytest.approx(0.4e-6)
    assert red.seconds("") == pytest.approx(0.8e-6)
    assert red.count("apex_flash_fwd") == 2
    assert red.seconds("all-reduce|all-gather") == pytest.approx(0.2e-6)


def test_two_devices_are_averaged():
    red = tr.Reduced({"/device:TPU:0": EVENTS,
                      "/device:TPU:1": [["fusion.1", 0, 2000]]}, 0, 2000)
    assert red.busy_s == pytest.approx((0.8e-6 + 2e-6) / 2)


def test_top_ops_sum_by_name():
    timed = tr.with_self_times(EVENTS + [[EVENTS[2][0], 1700, 500]])
    assert tr.top_ops(timed, 2) == [[EVENTS[2][0], pytest.approx(0.7e-6)],
                                    [FLASH, pytest.approx(0.4e-6)]]


def test_gaps_go_to_the_host_span_that_covers_most_of_them():
    # a span for a whole request covers every gap and wins none
    spans = SPANS + [["serve.request", 0, 1900]]
    gaps = dict(tr.idle_gaps(EVENTS, 0, 2000, spans))
    # gaps: [0,100) and [600,1000) under decode_step (the second only up
    # to 900, still most of it), [1200,1500) under prefill, [1600,2000)
    # under nothing
    assert gaps == {"serve.decode_step": pytest.approx(0.5e-6),
                    "serve.prefill": pytest.approx(0.3e-6),
                    "serve.request": pytest.approx(0.4e-6)}
    assert dict(tr.idle_gaps(EVENTS, 0, 2000, SPANS))["(no host span)"] \
        == pytest.approx(0.4e-6)


def test_clock_sync_moves_host_times_onto_the_trace():
    loaded = {"devices": {"d": EVENTS}, "sync_ns": 5000}
    # the annotation began at host time 100.0 s = trace time 5000 ns
    red = tr.reduce_trace(loaded, 100.0, (100.0 - 5e-6, 100.0 - 3e-6),
                          [["span", 100.0 - 4.9e-6, 0.9e-6]])
    assert (red.lo, red.hi) == (0, 2000)
    assert red.host_spans == [["span", 100, 900]]
    with pytest.raises(ValueError, match="clock"):
        tr.reduce_trace({"devices": {}, "sync_ns": None}, 1.0, (0, 1))


def test_readers_on_made_up_context():
    red = tr.Reduced({"d": EVENTS}, 0, 2000, SPANS)
    ctx = {"reduced": red, "counters": {"traced_steps": 2, "k": 3.0},
           "e2e": {"x": 7.0}, "notes": [],
           "spans": [{"name": "serve.decode_step", "ts": 0.0,
                      "dur_us": 900.0},
                     {"name": "serve.prefill", "ts": 0.00115,
                      "dur_us": 400.0},
                     {"name": "serve.decode_step", "ts": 0.002,
                      "dur_us": 1100.0}]}
    assert readers.device_ms_per_step(ctx, "apex_") \
        == pytest.approx(0.4e-6 * 1e3 / 2)
    assert readers.device_other_ms_per_step(ctx, ["apex_", "all-reduce"]) \
        == pytest.approx(0.2e-6 * 1e3 / 2)
    assert readers.device_idle_percent(ctx) == pytest.approx(60.0)
    assert readers.span_median_ms(ctx, "serve.decode_step") \
        == pytest.approx(1.0)
    assert readers.span_median_ms(ctx, "absent") is None
    # decode ends at 0.9 ms, prefill starts at 1.15 ms: 0.25 ms of host
    assert readers.span_gap_mean_ms(
        ctx, ["serve.decode_step"],
        ["serve.decode_step", "serve.prefill"]) == pytest.approx(0.25)
    assert readers.counter(ctx, "k", scale=2.0) == 6.0
    assert readers.counter(ctx, "absent") is None
    assert readers.e2e(ctx, "x") == 7.0
    with pytest.raises(ValueError, match="unknown reader"):
        readers.read({"name": "m", "reader": {"kind": "nope"}}, ctx)
    # a reader with nothing to read returns nothing
    empty = dict(ctx, reduced=None)
    assert readers.device_idle_percent(empty) is None
    assert readers.device_ms_per_step(empty, "apex_") is None


# ------------------------------------------------- the recorded v5e trace
@pytest.fixture(scope="module")
def recorded():
    return json.loads((REPO / "cellbench" / "trace" / "recorded_v5e.json")
                      .read_text())


def _sweep_busy(events):
    """The busy union by another road: count open intervals at every
    edge."""
    edges = sorted([(e[1], 1) for e in events]
                   + [(e[1] + e[2], -1) for e in events])
    busy = depth = 0
    for (t, d), (t_next, _) in zip(edges, edges[1:]):
        depth += d
        if depth > 0:
            busy += t_next - t
    return busy


def test_recorded_trace_busy_idle_and_kernel_time(recorded):
    (name, events), = recorded["devices"].items()
    assert name == "/device:TPU:0" and len(events) == 1500
    lo, hi = events[0][1], events[-1][1] + events[-1][2]
    assert (lo, hi) == (42957719, 141919361)
    red = tr.Reduced(recorded["devices"], lo, hi, modules=recorded["modules"])
    # unclipped, the union runs to the end of a loop that outlasts the
    # recording's last op; cut to the window it is the window
    assert tr.busy_ns(events) == _sweep_busy(events) == 236210508
    cut = tr.clip(events, lo, hi)
    assert tr.busy_ns(cut) == _sweep_busy(cut) == 98960802
    assert red.busy_s == pytest.approx(0.098960802)
    assert red.idle_share == pytest.approx(8.488e-06, rel=1e-3)
    # the kernels are found by their own names, jvp/transpose wrappers
    # included; the three loops add only their own 75 us
    assert red.count("apex_flash_fwd") == 27
    assert red.seconds("apex_flash_fwd") == pytest.approx(0.013300714)
    assert red.count("apex_") == 96
    assert red.seconds("apex_") == pytest.approx(0.038798002)
    assert red.count("while") == 3
    assert red.seconds("while") == pytest.approx(7.5324e-05)
    # self times add up to the busy time: the op line runs one at a time
    assert red.seconds("") == pytest.approx(red.busy_s)
    top = red.breakdown()["device_ops"]
    assert top[0][0].startswith("%apex_flash_fwd.12 = ")
    assert top[0][1] == pytest.approx(0.011819086)
    assert len(top) == 10 and all(a[1] >= b[1] for a, b in zip(top, top[1:]))


def test_recorded_trace_programs_and_gap_attribution(recorded):
    (name, events), = recorded["devices"].items()
    mods = recorded["modules"][name]
    assert all(m[0].startswith("jit_local_step(") for m in mods)
    # a step of GPT-2 medium at batch 8 took 322 ms on the device
    assert [round(m[2] / 1e6) for m in mods[:4]] == [322, 322, 322, 322]
    lo, hi = mods[0][1], mods[3][1] + mods[3][2]
    red = tr.Reduced(recorded["devices"], lo, hi, modules=recorded["modules"])
    assert red.programs("jit_local_step") == 4
    # the recording ends with a loop at 279.2 ms: what follows is idle
    # as far as it can tell, and goes to the host span that covers it
    end = max(e[1] + e[2] for e in events)
    assert end == 279169067
    lo2, hi2 = events[0][1], end + 1_000_000
    spans = [["train.step.dispatch", hi2 - 900_000, 900_000]]
    gaps = dict(tr.idle_gaps(events, lo2, hi2, spans))
    assert gaps["train.step.dispatch"] == pytest.approx(1e-3, rel=1e-3)
    assert sum(gaps.values()) == pytest.approx(1e-3 + 8.4e-07, rel=1e-2)


def test_recorded_trace_clock_sync(recorded):
    (name, events), = recorded["devices"].items()
    sync = recorded["sync_ns"]
    assert sync == 42499497 and sync < events[0][1]
    # host time 1000.0 s at the annotation; a window of the next 50 ms
    red = tr.reduce_trace(recorded, 1000.0, (1000.0, 1000.05))
    assert (red.lo, red.hi) == (sync, sync + 50_000_000)
    first = events[0][1]
    assert red.busy_s == pytest.approx((red.hi - first) / 1e9, rel=1e-4)


def test_requests_due_while_the_profiler_holds_the_loop_are_left_out():
    """Starting and stopping the profiler stalls the serving loop: a
    traced run's first-token readings skip what was due inside a stall
    or the settling after it, and an untraced run skips nothing."""
    from cellbench.profiling import WindowTrace

    wt = WindowTrace(False, "unused", 15.0, 4.0)
    assert wt.undisturbed(0.0) and wt.undisturbed(1e9)
    wt.stalls += [(100.0, 103.4), (107.4, 108.5)]
    assert [wt.undisturbed(t) for t in
            (99.9, 100.0, 103.4, 105.3, 105.5, 107.3, 108.6, 110.4, 110.6)] \
        == [True, False, False, False, True, True, False, False, True]
