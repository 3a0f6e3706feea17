"""The per-layer metrics that read the serving scheduler's spans
(``cellbench/span_readers.py``): each reader on the spans of a traced
``tiny.chat`` run on the CPU (counts and orders, never a time that is
reported), the profiler-stall rule on a few made-up spans, and the idle
split on a made-up device trace with a known answer."""

import json

import pytest

from cellbench_tiny import REPO, make_root

from cellbench import arith, readers, span_readers
from cellbench.cells import Bench
from cellbench.run import run_cell
from cellbench.trace import reduce as tr

SEED = 2 ** 31 + 77
SPAN_METRICS = ["ttft_queue_p90.chat", "ttft_prefill_p90.chat",
                "prefill_span.chat", "steps_after_prefill.chat"]
IDLE_METRICS = {"idle_in_call.chat": "in_call",
                "idle_in_call.over": "in_call",
                "idle_between_calls.chat": "between_calls",
                "idle_between_calls.over": "between_calls"}


def _read(bench, name, ctx):
    own = json.loads((bench.data / "layer_metrics" / f"{name}.json")
                     .read_text())
    return readers.read(own, ctx, bench.custom_reader(name))


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """A traced tiny.chat run; the adapter's tracer stays installed, as
    it is when the harness calls the readers."""
    from apex_tpu.observability import tracing

    root = make_root(tmp_path_factory.mktemp("spans"))
    out = run_cell(root, "tiny.chat", SEED, 2.0, True, require_tpu=False)
    assert out["correct"] is True
    spans = span_readers.program_spans()
    yield Bench(root), spans
    tracing.disable()


def _ctx(spans):
    return {"spans": spans, "reduced": None, "counters": {}, "notes": []}


@pytest.mark.parametrize("name", SPAN_METRICS)
def test_a_span_reader_reads_the_traced_tiny_run(traced, name):
    bench, spans = traced
    value = _read(bench, name, _ctx(spans))
    requests = span_readers.window_requests(spans)
    assert len(requests) == 12            # 6 a second for 2 seconds
    stalled = span_readers.stalls(spans)
    kept = [r for r in requests if not span_readers.disturbed(
        r["ts"], r["ts"] + r["attrs"]["ttft_s"], stalled)]
    assert kept
    if name.startswith("ttft_"):
        key = "queue_s" if "queue" in name else "prefill_s"
        assert value == pytest.approx(arith.percentile(
            [1e3 * r["attrs"][key] for r in kept], 90))
        assert min(1e3 * r["attrs"][key] for r in kept) <= value \
            <= max(1e3 * r["attrs"][key] for r in kept)
    elif name == "prefill_span.chat":
        durs = sorted(s["dur_us"] / 1e3 for s in spans
                      if s["name"] == "serve.prefill")
        assert durs[0] <= value <= durs[-1]
        # the span holds the wait for the first token, not the enqueue
        # alone
        assert all(s["attrs"]["dispatch_us"] <= s["dur_us"]
                   for s in spans if s["name"] == "serve.prefill")
    else:
        assert 0 < value <= 100
        # every prefill is charged to one step, so steps that followed
        # one are at most the prefills
        steps = [s for s in spans if s["name"] == "serve.decode_step"]
        after = sum(1 for s in steps if s["attrs"]["prefills_before"])
        assert 1 <= after <= sum(1 for s in spans
                                 if s["name"] == "serve.prefill")


@pytest.mark.parametrize("name", SPAN_METRICS)
def test_a_program_without_the_new_attributes_reads_as_nothing(
        traced, name, monkeypatch):
    """The parent commit has the spans and lacks the attributes: the
    readers return None and do not raise (``prefill_span`` reads the
    span's length, which the parent has too)."""
    bench, spans = traced
    old = [dict(s, attrs={k: v for k, v in s["attrs"].items()
                          if k not in ("queue_s", "prefill_s", "blocked_on",
                                       "prefills_before", "dispatch_us")})
           for s in spans if s["name"] not in ("serve.admit", "serve.emit")]
    monkeypatch.setattr(span_readers, "program_spans", lambda: old)
    value = _read(bench, name, _ctx(old))
    assert (value is None) == (name != "prefill_span.chat")
    monkeypatch.setattr(span_readers, "program_spans", lambda: [])
    assert _read(bench, name, _ctx([])) is None


def _span(name, ts, dur_s, **attrs):
    return {"name": name, "ts": ts, "dur_us": int(dur_s * 1e6),
            "attrs": attrs}


def test_requests_the_profiler_disturbed_are_left_out():
    """Decode steps every 0.1 s, none from 10.0 to 13.4 (the profiler
    starting).  A request is left out when submit -> first token
    touches that stretch or the 2 s after it."""
    steps = [_span("serve.decode_step", 0.1 * i, 0.09, prefills_before=0)
             for i in range(100)]
    steps += [_span("serve.decode_step", 13.4 + 0.15 * i, 0.09,
                    prefills_before=int(i % 2 == 0)) for i in range(100)]
    assert span_readers.stalls(steps) == [
        (pytest.approx(9.99), pytest.approx(13.4))]

    def request(rid, submit, queue_s):
        return _span("serve.request", submit, 9.0, rid=rid, queue_s=queue_s,
                     prefill_s=0.06, ttft_s=queue_s + 0.06,
                     blocked_on=None)

    spans = steps + [
        request(0, 1.95, 0.010),          # long before
        request(1, 9.95, 0.020),          # its first token falls inside
        request(2, 11.0, 2.500),          # submitted inside
        request(3, 15.0, 0.900),          # the backlog, within 2 s after
        request(4, 15.52, 0.030),         # after the settling
        request(10 ** 9 + 3, 16.0, 5.0),  # a warm-up request
    ]
    assert span_readers.first_token_part_p90_ms(spans, "queue_s") \
        == pytest.approx(arith.percentile([10.0, 30.0], 90))
    assert span_readers.first_token_part_p90_ms(spans, "prefill_s") \
        == pytest.approx(60.0)
    # steps between the first and the last submit of a window request
    # (1.95 .. 15.52), less those inside the stall's settling (up to
    # 15.4): the 80 from 2.0 to 9.9, none of which followed a prefill,
    # and the one at 15.5, which did
    share = span_readers.steps_after_prefill_percent(spans)
    assert share == pytest.approx(100.0 * 1 / 81)


# one device, times in ns: busy 100-600 and 1000-1200 in a stretch of
# 0-2000, so idle 0-100, 600-1000 and 1200-2000 (1,300 ns in all)
BUSY = [["%fusion.1 = bf16[8] fusion(%p)", 100, 500],
        ["%fusion.2 = bf16[8] fusion(%p)", 1000, 200]]
HOST = [["serve.decode_step", 0, 700],    # launch 0-100, readback 600-700
        ["serve.emit", 700, 250],         # bookkeeping 700-950
        ["serve.verify_step", 1200, 400],  # a call, 1200-1600 ...
        ["serve.emit", 1450, 150],        # ... that holds its bookkeeping
        ["serve.request", 0, 2000]]       # whole life: covers everything


@pytest.mark.parametrize("name", sorted(IDLE_METRICS))
def test_idle_time_splits_into_inside_and_between_device_calls(name):
    red = tr.Reduced({"/device:TPU:0": BUSY}, 0, 2000, HOST)
    ctx = {"reduced": red, "counters": {"traced_steps": 2}, "spans": [],
           "notes": []}
    split = span_readers.idle_split_ms_per_step(ctx)
    # inside a call: 0-100, 600-700 and 1200-1450; between calls:
    # 700-1000 (bookkeeping, then no span), 1450-1600 (bookkeeping
    # nested in the verify step) and 1600-2000
    assert split["in_call"] == pytest.approx(450e-6 / 2)
    assert split["between_calls"] == pytest.approx(850e-6 / 2)
    idle_ms = 1e3 * red.idle_share * red.window_s
    assert split["in_call"] + split["between_calls"] \
        == pytest.approx(idle_ms / 2)
    # gap by gap, ``reduce.idle_gaps`` gives the whole of 600-1000 to
    # serve.emit and the whole of 1200-2000 to the verify step, which
    # covers just half of it: a gap has one owner there, and here each
    # moment has
    gaps = dict(tr.idle_gaps(BUSY, 0, 2000, HOST))
    assert gaps["serve.emit"] == pytest.approx(400e-9)
    assert gaps["serve.verify_step"] == pytest.approx(800e-9)
    assert _read(Bench(REPO), name, ctx) == split[IDLE_METRICS[name]]
    # nothing to read: no device trace, or no step inside the stretch
    assert _read(Bench(REPO), name, dict(ctx, reduced=None)) is None
    assert _read(Bench(REPO), name, dict(ctx, counters={})) is None
