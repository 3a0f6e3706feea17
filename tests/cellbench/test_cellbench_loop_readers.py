"""The per-layer metrics that take the serve loop's iteration apart
(``cellbench/loop_readers.py``): each reader on the spans of a traced
``tiny.chat`` run on the CPU (counts and orders, never a time that is
reported), on hand-made spans and a hand-made device trace with known
answers (a stall in the middle, an empty stretch, a bucket the stretch
never ran), and None on spans without the attributes of PR 35."""

import json

import pytest

from cellbench_tiny import REPO, make_root

from cellbench import loop_readers, readers, span_readers
from cellbench.cells import Bench
from cellbench.run import run_cell
from cellbench.trace import reduce as tr

SEED = 2 ** 31 + 79
SPAN_METRICS = ["host_iter.chat", "host_iter.serve", "launch_upload.chat",
                "launch_upload.serve", "prefill_padding.chat",
                "prefill_padding.serve"]
TRACE_METRICS = ["prefill_stall.chat", "prefill_stall.serve",
                 "prefill_share_window.serve"]
NEW_ATTRS = ("prep_us", "upload_us", "enqueue_us", "wait_us", "behind_step")


def _read(name, ctx):
    bench = Bench(REPO)
    own = json.loads((bench.data / "layer_metrics" / f"{name}.json")
                     .read_text())
    return readers.read(own, ctx, bench.custom_reader(name))


def _ctx(spans=(), reduced=None):
    return {"spans": list(spans), "reduced": reduced, "counters": {},
            "notes": []}


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """The spans of a traced tiny.chat run (the adapter's tracer stays
    installed, as it is when the harness calls the readers)."""
    from apex_tpu.observability import tracing

    root = make_root(tmp_path_factory.mktemp("loop"))
    out = run_cell(root, "tiny.chat", SEED, 2.0, True, require_tpu=False)
    assert out["correct"] is True
    yield span_readers.program_spans()
    tracing.disable()


@pytest.mark.parametrize("name", SPAN_METRICS)
def test_a_loop_reader_reads_the_traced_tiny_run(traced, name):
    ctx = _ctx()
    value = _read(name, ctx)
    steps = [s for s in traced if s["name"] == "serve.decode_step"
             and s["attrs"]["in_flight"]]
    assert steps and value is not None
    if name.startswith("host_iter"):
        # the identity the metric is made of: host + waits = the period
        (note,) = ctx["notes"]
        period, host, wait, pre = (
            float(note.split(key)[1].split()[0]) for key in
            ("mean ", "host ", "the step ", "for prefills "))
        assert host == pytest.approx(value, abs=1e-3)
        assert host + wait + pre == pytest.approx(period, abs=2e-3)
        assert 0 < value < period
    elif name.startswith("launch_upload"):
        assert 0 < value <= max(s["attrs"]["dispatch_us"]
                                for s in steps) / 1e3
        assert "dispatch_us" in ctx["notes"][0]
    else:
        # tiny.chat compiles one length, 32, for prompts of 4 to 32
        kept = loop_readers._kept(traced, "serve.prefill", "wait_us")
        assert kept and {s["attrs"]["padded_tokens"] for s in kept} == {32}
        assert 0 <= value < 100
        assert value == pytest.approx(100 * (1 - sum(
            s["attrs"]["tokens"] for s in kept) / (32 * len(kept))))


@pytest.mark.parametrize("name", SPAN_METRICS + TRACE_METRICS)
def test_a_program_without_the_new_attributes_reads_as_nothing(
        traced, name, monkeypatch):
    """The parent commit has the spans, ``tokens``, ``padded_tokens``
    and ``in_flight``, and lacks what PR 35 added: every reader returns
    None and none raises."""
    old = [dict(s, attrs={k: v for k, v in s["attrs"].items()
                          if k not in NEW_ATTRS})
           for s in traced if s["name"] != "serve.idle"]
    monkeypatch.setattr(span_readers, "program_spans", lambda: old)
    inside = [s for s in old if s["name"] == "serve.prefill"][:2]
    red = _reduced(old, base=inside[0]["ts"])
    assert _read(name, _ctx(inside, red)) is None
    assert _read(name, _ctx(old)) is None
    monkeypatch.setattr(span_readers, "program_spans", lambda: [])
    assert _read(name, _ctx()) is None


# ------------------------------------------------ hand-made, known answers
def _span(name, ts, dur_s, **attrs):
    return {"name": name, "ts": ts, "dur_us": int(round(dur_s * 1e6)),
            "attrs": attrs}


def _step(ts, in_flight=1, wait_us=6000, upload_us=400, dispatch_us=1500):
    return _span("serve.decode_step", ts, 0.0075, in_flight=in_flight,
                 prefills_before=0, prep_us=300, upload_us=upload_us,
                 enqueue_us=dispatch_us - upload_us,
                 dispatch_us=dispatch_us, wait_us=wait_us)


def _prefill(ts, dur_s, tokens, padded, rid=0, wait_us=20_000):
    return _span("serve.prefill", ts, dur_s, rid=rid, tokens=tokens,
                 padded_tokens=padded, upload_us=200, enqueue_us=800,
                 dispatch_us=1000, wait_us=wait_us, behind_step=1)


def _request(rid, ts):
    return _span("serve.request", ts, 9.0, rid=rid, queue_s=0.01,
                 prefill_s=0.05, ttft_s=0.06, blocked_on=None)


def _window_spans():
    """A window from 0.995 to 29.005 s.  Decode steps every 10 ms, each
    waiting 6 ms for the device: the host's part of a period is 4 ms.
    From 5.0 s one prefill (20 ms of wait) sits between two steps, whose
    period is 30 ms: still 4 ms of host.  From 10.0 to 13.4 s no step
    runs (the profiler starting), and the backlog after it (until 15.4)
    runs periods of 20 ms that must not count.  At 20.0 s the server
    runs empty for 0.5 s: the step after it has ``in_flight`` 0, and
    the pairs around the stretch do not count either."""
    spans = [_request(0, 0.995), _request(1, 29.005),
             _request(10 ** 9 + 5, 0.2)]
    spans += [_step(0.5 + 0.01 * i) for i in range(450)]      # to 4.99
    spans += [_step(5.0), _prefill(5.008, 0.0215, 40, 64), _step(5.03)]
    spans += [_step(5.04 + 0.01 * i) for i in range(496)]     # to 9.99
    spans += [_step(13.4 + 0.02 * i, wait_us=1000, upload_us=900)
              for i in range(100)]                            # to 15.38
    spans += [_step(15.4 + 0.01 * i) for i in range(460)]     # to 19.99
    spans += [_span("serve.idle", 20.0, 0.5, polls=400),
              _step(20.5, in_flight=0)]
    spans += [_step(20.51 + 0.01 * i) for i in range(900)]    # to 29.5
    # further prefills (no wait: the steps around them stay as they
    # are): two undisturbed, one in the backlog, one before the window
    spans += [_prefill(8.0, 0.001, 100, 128, rid=2, wait_us=0),
              _prefill(14.0, 0.001, 1, 512, rid=3, wait_us=0),
              _prefill(25.0, 0.001, 52, 64, rid=4, wait_us=0),
              _prefill(0.3, 0.001, 7, 64, rid=10 ** 9 + 5, wait_us=0)]
    return spans


def test_the_hosts_iteration_leaves_out_stalls_and_an_empty_server():
    spans = _window_spans()
    notes = []
    assert span_readers.stalls(spans) == [
        (pytest.approx(9.9975), pytest.approx(13.4))]
    value = loop_readers.host_iter_ms(spans, notes)
    assert value == pytest.approx(4.0, abs=1e-6)
    # mean period 10 ms but for the one pair that holds the prefill
    n = int(notes[0].split()[1])
    # pairs that start at 1.0-9.98 (898 steps), at 15.41 (the settling
    # ends at 15.4) to 19.98, and at 20.51-29.00
    assert n == 897 + 458 + 850
    assert "wait for the step 6.000" in notes[0]
    assert loop_readers.launch_upload_ms(spans, notes) \
        == pytest.approx(0.4)
    assert "mean dispatch_us 1.500 ms, prep_us 0.300 ms" in notes[1]


def test_padding_counts_the_windows_undisturbed_prefills(monkeypatch):
    spans = _window_spans()
    # 40 of 64 at 5.008, 100 of 128 at 8.0, 52 of 64 at 25.0; not the
    # backlog's (1 of 512) nor the one before the window opened
    assert loop_readers.prefill_padding_percent(spans) \
        == pytest.approx(100 * (1 - 192 / 256))
    assert _read_with(monkeypatch, spans, "prefill_padding.serve") \
        == pytest.approx(25.0)


def _read_with(monkeypatch, spans, name, ctx=None):
    """``name`` read with ``spans`` as the program's own buffer."""
    monkeypatch.setattr(span_readers, "program_spans", lambda: spans)
    return _read(name, ctx or _ctx())


# one device, a stretch of 0-1000 ms on the trace's clock (ns)
MS = 1_000_000
DEVICE = [["%step", 0 * MS, 100 * MS],        # step n+1, in flight
          ["%prefill", 100 * MS, 50 * MS],    # queued behind it
          ["%step", 160 * MS, 100 * MS],      # relaunched 10 ms late
          ["%step", 262 * MS, 100 * MS],
          ["%prefill", 400 * MS, 70 * MS],    # on an empty server
          ["%step", 480 * MS, 100 * MS]]
PROGRAMS = [["jit_step(1)", 0 * MS, 100 * MS],
            ["jit_prefill(2)", 100 * MS, 50 * MS],
            ["jit__set_token(3)", 151 * MS, 1],
            ["jit_step(1)", 160 * MS, 100 * MS],
            ["jit_step(1)", 262 * MS, 100 * MS],
            ["jit_prefill(2)", 400 * MS, 70 * MS],
            ["jit_step(1)", 480 * MS, 100 * MS]]
#: the host's spans, in s after ``BASE`` on the host's clock, which is
#: where the traced stretch starts (inside the window above, after the
#: stall's settling)
BASE = 16.0
HOST = [_span("serve.decode_step", BASE + 0.000, 0.005, in_flight=1),
        _span("serve.admit", BASE + 0.010, 0.145),
        _prefill(BASE + 0.012, 0.142, 40, 64),    # ends at its readback
        _span("serve.decode_step", BASE + 0.157, 0.004, in_flight=1),
        _span("serve.emit", BASE + 0.161, 0.002),
        _span("serve.decode_step", BASE + 0.258, 0.006, in_flight=1),
        _span("serve.idle", BASE + 0.370, 0.020, polls=15),
        _span("serve.admit", BASE + 0.392, 0.082),
        _prefill(BASE + 0.395, 0.078, 100, 128),
        _span("serve.decode_step", BASE + 0.476, 0.005, in_flight=0)]


def _reduced(host=HOST, base=BASE):
    """The device trace above with ``host`` on its clock, moved there as
    ``reduce.reduce_trace`` moves spans (the stretch starts at ``base``
    on the host's clock)."""
    off = -base * 1e9
    return tr.Reduced(
        {"/device:TPU:0": DEVICE}, 0, 1000 * MS,
        [[s["name"], int(s["ts"] * 1e9 + off), int(s["dur_us"] / 1e6 * 1e9)]
         for s in host],
        {"/device:TPU:0": PROGRAMS})


@pytest.mark.parametrize("name", TRACE_METRICS[:2])
def test_a_prefills_stall_is_the_devices_idle_around_it(name):
    """The device idles 150-160 (the first prefill's readback and the
    late relaunch: from the admit's start, 10, to the end of the step
    after it, 161), 362-400 of which 392-400 lies under the second
    admit (the launch on an empty server; 370-390 was an empty server's
    and is not the prefill's), 470-480 (readback and relaunch, to 481),
    and 580-1000 (nothing to do).  Two prefills: (10 + 8 + 10) / 2."""
    red = _reduced()
    assert _read(name, _ctx(HOST, red)) == pytest.approx(14.0)
    assert red.idle_share * red.window_s * 1e3 == pytest.approx(480.0)
    assert _read(name, _ctx(HOST, None)) is None
    # a stretch without a prefill has nothing to divide by
    quiet = [s for s in HOST if s["name"] != "serve.prefill"]
    assert _read(name, _ctx(HOST, _reduced(quiet))) is None


def test_the_prefills_share_of_the_whole_window(monkeypatch):
    """The stretch ran a 64 (50 ms on the device) and a 128 (70 ms);
    the window's undisturbed prefills are a 64, a 128 and another 64
    (the backlog's 512 is left out with the stall), over 28.01 s less
    the 5.4 s from the stall's start to the end of its settling."""
    window = _window_spans()
    ctx = _ctx(HOST, _reduced())
    value = _read_with(monkeypatch, window, "prefill_share_window.serve", ctx)
    seconds = 28.01 - (15.4 - 9.9975)
    assert value == pytest.approx(
        100 * (0.050 + 0.070 + 0.050) / seconds, rel=1e-6)
    assert not [n for n in ctx["notes"] if "ran no prefill" in n]
    # a bucket the stretch never ran: the nearest one's time, scaled
    more = window + [_prefill(26.0, 0.3, 200, 256, rid=6)]
    ctx = _ctx(HOST, _reduced())
    value = _read_with(monkeypatch, more, "prefill_share_window.serve", ctx)
    assert value == pytest.approx(
        100 * (0.050 + 0.070 + 0.050 + 0.140) / seconds, rel=1e-6)
    (note,) = [n for n in ctx["notes"] if "ran no prefill" in n]
    assert "256" in note and "128" in note and "2.000" in note
    # no device trace, no programs in it, or no prefill in the stretch
    assert _read_with(monkeypatch, window, "prefill_share_window.serve",
                      _ctx(HOST, None)) is None
    bare = tr.Reduced({"/device:TPU:0": DEVICE}, 0, 1000 * MS, [])
    assert _read_with(monkeypatch, window, "prefill_share_window.serve",
                      _ctx(HOST, bare)) is None
    quiet = [s for s in HOST if s["name"] != "serve.prefill"]
    assert _read_with(monkeypatch, window, "prefill_share_window.serve",
                      _ctx(quiet, _reduced(quiet))) is None
