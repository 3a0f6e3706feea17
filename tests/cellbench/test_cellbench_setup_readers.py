"""The per-layer metric that takes ``setup_s`` apart
(``cellbench/setup_readers.py``, ``trace_lower.setup``): the union, the
boundary, the cache split and the top-five note on made-up spans with
known answers, and the reader on the program's own buffer after a
traced tiny run on the CPU, bounded by the window's own step spans as
the traced stretch bounds it on the chip (counts and orders, never a
time that is reported)."""

import json

import pytest

from cellbench_tiny import make_root

from cellbench import readers, setup_readers, span_readers
from cellbench.cells import Bench
from cellbench.run import run_cell

SEED = 2 ** 31 + 49
NAME = "trace_lower.setup"
_IDS = iter(range(1, 10 ** 6))


def _span(name, ts, dur_s, parent=None, tid=1, **attrs):
    return {"name": name, "ts": ts, "dur_us": int(round(dur_s * 1e6)),
            "tid": tid, "id": next(_IDS), "parent": parent, "attrs": attrs}


def _program(fun, ts, trace, lower, backend, cache="hit", parent=None,
             tid=1):
    """One program's three spans, back to back from ``ts``."""
    return [
        _span("compile.trace", ts, trace, parent, tid, fun_name=fun),
        _span("compile.lower", ts + trace, lower, parent, tid,
              fun_name=f"jit({fun})"),
        _span("compile.backend", ts + trace + lower, backend, parent, tid,
              fun_name=f"jit({fun})", cache=cache)]


def _ctx(inside, setup_s=None):
    return {"spans": inside, "notes": [],
            "e2e": {} if setup_s is None else {"setup_s": setup_s}}


def _stretch(lo, hi):
    """A traced stretch: what ``WindowTrace.spans_inside`` hands over."""
    return [_span("serve.decode_step", lo, 0.02),
            _span("serve.decode_step", hi - 0.02, 0.02)]


def test_nested_and_overlapping_spans_count_once_a_thread():
    spans = [
        _span("compile.trace", 10.0, 4.0, fun_name="outer"),
        _span("compile.trace", 11.0, 1.0, fun_name="inner"),    # nested
        _span("compile.lower", 13.5, 1.5, fun_name="jit(outer)"),  # overlaps
        _span("compile.trace", 20.0, 2.0, fun_name="next"),
        # another thread compiles at the same time: its own seconds
        _span("compile.trace", 10.5, 3.0, tid=2, fun_name="other"),
    ]
    assert setup_readers.union_s(spans) == pytest.approx(5.0 + 2.0 + 3.0)
    assert setup_readers.union_s(spans[:2]) == pytest.approx(4.0)
    assert setup_readers.union_s([]) == 0.0
    value = setup_readers.trace_lower_s(spans, _ctx(_stretch(40.0, 44.0)), 0)
    assert value == pytest.approx(10.0)


def test_only_what_ended_before_the_traced_stretch_counts():
    prefill = _span("serve.prefill", 9.9, 6.0, padded_tokens=512)
    warm = _program("prefill", 10.0, 2.0, 1.0, 0.5, parent=prefill["id"])
    # a shape that drifted in service: inside the stretch
    drift = _program("step", 41.0, 0.3, 0.2, 0.1, cache="miss")
    # the reference's own programs, after the close
    after = _program("reference", 60.0, 5.0, 5.0, 5.0, cache="miss")
    spans = [prefill] + warm + drift + after
    ctx = _ctx(_stretch(40.0, 44.0), setup_s=20.0)
    assert setup_readers.trace_lower_s(spans, ctx, 0) == pytest.approx(3.0)
    totals, top = ctx["notes"]
    assert "trace 2.00 s + lower 1.00 s in 1 programs" in totals
    assert "3 compile spans inside the window" in totals
    # set-up less every compile span before the stretch: 20 - 3.5
    assert "16.50 s of 20.00" in totals
    assert "reference" not in top and "step" not in top
    # one that straddles the stretch's start is inside it, not before
    straddle = [_span("compile.lower", 39.5, 1.0, fun_name="jit(late)")]
    ctx = _ctx(_stretch(40.0, 44.0))
    assert setup_readers.trace_lower_s(warm + straddle, ctx, 0) \
        == pytest.approx(3.0)
    assert "1 compile spans inside the window" in ctx["notes"][0]
    assert "not known" in ctx["notes"][0]


def test_the_backend_seconds_split_by_what_the_cache_did():
    spans = (_program("a", 1.0, 0.1, 0.1, 0.8, cache="hit")
             + _program("b", 3.0, 0.1, 0.1, 0.4, cache="hit")
             + _program("c", 5.0, 0.1, 0.1, 7.0, cache="miss")
             + _program("d", 15.0, 0.1, 0.1, 0.25, cache="off"))
    ctx = _ctx(_stretch(40.0, 44.0), setup_s=30.0)
    assert setup_readers.trace_lower_s(spans, ctx, 0) == pytest.approx(0.8)
    assert ("backend hit 1.20 s (2 programs), miss 7.00 s (1 programs), "
            "off 0.25 s (1 programs)") in ctx["notes"][0]
    assert "0 compile spans inside the window" in ctx["notes"][0]
    assert "tracer dropped 0, failed to record 0" in ctx["notes"][0]
    assert f"{30.0 - 0.8 - 8.45:.2f} s of 30.00" in ctx["notes"][0]


def test_the_top_five_are_named_by_program_and_caller():
    buckets = [_span("serve.prefill", 10.0 * i, 9.0, padded_tokens=128 * i)
               for i in (1, 2, 3, 4)]
    step = _span("serve.decode_step", 50.0, 5.0)
    spans = buckets + [step]
    for i, b in enumerate(buckets):
        spans += _program("prefill", b["ts"] + 0.1, 1.0 + i, 0.5, 0.25,
                          parent=b["id"])
    spans += _program("step", 50.1, 0.7, 0.2, 0.1, parent=step["id"])
    spans += _program("born", 60.0, 0.05, 0.02, 0.01)           # no span
    spans += _program("tiny", 61.0, 0.01, 0.01, 0.01)          # the sixth
    top = setup_readers.top_programs(
        [s for s in spans if s["name"] in setup_readers.COMPILE], spans)
    assert [(fun, caller) for fun, caller, _ in top] == [
        ("prefill", "512 tokens"), ("prefill", "384 tokens"),
        ("prefill", "256 tokens"), ("prefill", "128 tokens"),
        ("step", "serve.decode_step")]
    assert top[0][2] == {"compile.trace": pytest.approx(4.0),
                         "compile.lower": pytest.approx(0.5),
                         "compile.backend": pytest.approx(0.25)}
    ctx = _ctx(_stretch(80.0, 84.0))
    setup_readers.trace_lower_s(spans, ctx, 0)
    assert ctx["notes"][1].startswith(
        "set-up's compile seconds, the most: prefill under 512 tokens: "
        "trace 4.00 + lower 0.50 + backend 0.25; prefill under 384 tokens")
    assert "born" not in ctx["notes"][1]
    assert setup_readers.program_of(spans[-1]) == "tiny"
    assert setup_readers.program_of(spans[-3]) == "tiny"


@pytest.mark.parametrize("case", ["no compile spans", "dropped spans",
                                  "listener errors", "no traced stretch"])
def test_nothing_to_read_is_none_and_does_not_raise(case):
    """A parent commit older than the spans, a ring that overflowed, a
    listener that failed, a run that took no device trace (nothing says
    where set-up ended)."""
    warm = _program("prefill", 10.0, 2.0, 1.0, 0.5)
    stretch = _stretch(40.0, 44.0)
    if case == "no compile spans":
        assert setup_readers.trace_lower_s(stretch, _ctx(stretch), 0) is None
        assert setup_readers.trace_lower_s([], _ctx([]), 0) is None
    elif case == "dropped spans":
        ctx = _ctx(stretch)
        assert setup_readers.trace_lower_s(warm, ctx, 3) is None
        assert "tracer dropped 3" in ctx["notes"][0]     # and says why
    elif case == "listener errors":
        ctx = _ctx(stretch)
        assert setup_readers.trace_lower_s(warm, ctx, 0, 2) is None
        assert "failed to record 2" in ctx["notes"][0]
    else:
        ctx = _ctx([])
        assert setup_readers.trace_lower_s(warm, ctx, 0) is None
        assert not ctx["notes"]


# ---------------------------------------------------- the traced tiny run
def _read(bench, ctx):
    own = json.loads((bench.data / "layer_metrics" / f"{NAME}.json")
                     .read_text())
    return readers.read(own, ctx, bench.custom_reader(NAME))


@pytest.fixture(scope="module", params=["tiny.chat", "tiny.train"])
def traced(request, tmp_path_factory):
    """A traced tiny run; the adapter's tracer stays installed, as it
    is when the harness calls the readers."""
    from apex_tpu.observability import tracing

    root = make_root(tmp_path_factory.mktemp("setup"))
    out = run_cell(root, request.param, SEED, 2.0, True, require_tpu=False)
    assert out["correct"] is True
    yield Bench(root), span_readers.program_spans(), out
    tracing.disable()


def _window_steps(spans, out):
    """The window's own step spans: what the traced stretch holds on
    the chip.  Decode steps from the first window request's submit, or
    the run's last ``attempted`` train steps."""
    requests = span_readers.window_requests(spans)
    if requests:
        opened = min(r["ts"] for r in requests)
        return [s for s in spans if s["name"] == "serve.decode_step"
                and s["ts"] >= opened]
    steps = sorted((s for s in spans if s["name"] == "train.step.dispatch"),
                   key=lambda s: s["ts"])
    return steps[-out["attempted"]:]


def test_the_reader_reads_the_programs_own_buffer(traced):
    from apex_tpu.observability import tracing

    bench, spans, out = traced
    compiles = [s for s in spans if s["name"] in setup_readers.COMPILE]
    tracer = tracing.get_tracer()
    assert compiles and tracer.dropped == 0 and tracer.compile_errors == 0
    inside = _window_steps(spans, out)
    assert len(inside) >= 2
    ctx = _ctx(inside)
    value = _read(bench, ctx)
    opened = min(s["ts"] for s in inside)
    before = [s for s in compiles if s["name"] != "compile.backend"
              and s["ts"] + s["dur_us"] / 1e6 <= opened]
    # (an epoch second holds a quarter of a microsecond: the slack)
    assert 0 < value <= sum(s["dur_us"] for s in before) / 1e6 + 1e-3
    totals, top = ctx["notes"]
    assert " 0 compile spans inside the window" in totals
    assert "tracer dropped 0, failed to record 0" in totals
    assert top.count(": trace ") == 5
    # whole programs only: a few hundred spans, and on one thread no
    # two of them overlap (what a program's trace contains is its time)
    assert len(compiles) < 1000
    by_thread = {}
    for s in compiles:
        by_thread.setdefault(s["tid"], []).append(s)
    for mine in by_thread.values():
        mine.sort(key=lambda s: s["ts"])
        for a, b in zip(mine, mine[1:]):
            assert a["ts"] + a["dur_us"] / 1e6 <= b["ts"] + 1e-4


def test_a_warm_up_call_reads_as_its_compile_children_and_the_rest(traced):
    """The first call of each warmed program has all three children,
    inside it; a bucket is named by its caller's attributes."""
    bench, spans, out = traced
    compiles = [s for s in spans if s["name"] in setup_readers.COMPILE]
    by_id = {s["id"]: s for s in spans}
    serving = any(s["name"] == "serve.prefill" for s in spans)
    callers = (["serve.prefill", "serve.decode_step"] if serving
               else ["train.step.dispatch"])
    for name in callers:
        first = min((s for s in spans if s["name"] == name),
                    key=lambda s: s["ts"])
        kids = [s for s in compiles if s["parent"] == first["id"]]
        assert {s["name"] for s in kids} == set(setup_readers.COMPILE)
        assert sum(s["dur_us"] for s in kids) <= first["dur_us"]
        for s in kids:
            assert first["ts"] - 1e-3 <= s["ts"]
            assert s["ts"] + s["dur_us"] / 1e6 \
                <= first["ts"] + first["dur_us"] / 1e6 + 1e-3
    for s in compiles:
        if s["parent"] is not None:
            assert by_id[s["parent"]]["name"] in (
                "serve.prefill", "serve.decode_step", "serve.admit",
                "train.step.dispatch")
    if serving:
        top = setup_readers.top_programs(compiles, spans, n=50)
        assert ("prefill", "32 tokens") in [(f, c) for f, c, _ in top]
