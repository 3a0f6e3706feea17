"""Distributed tracing, flight recorder, and anomaly detection
(apex_tpu.observability v2, ISSUE 14).

The load-bearing bands:

- **Observer, never participant**: tracing on vs off produces
  BITWISE-identical loss/params on the real ``make_train_step``
  (replicated+clip, ZeRO+clip, hierarchical int8 sync) — the
  :class:`~apex_tpu.observability.tracing.TracedStep` wrapper lives
  entirely outside jit (the lowering side of the same contract is
  pinned in tests/test_lowered_invariants.py::TestTracingTrainStep).
- **Forensics chaos matrix**: the dump triggers really fire — a
  watchdog wedge dumps a recording whose OPEN span is the wedged
  dispatch with the right ``(run_id, step)``, a StepGuard budget abort
  and a preemption notice each leave a reason-stamped dump, and
  torn/partial dump files are skipped LOUDLY on read.
- **Exporters**: the Chrome-trace export is Perfetto-loadable JSON
  (phase/ts/dur/args shape, thread_name metadata), the JSONL export
  carries the sidecar contract fields, and both carry the
  ``(run_id, step)`` correlation captured at span START.
- **Anomaly detection**: rolling median/MAD robust z-scores alarm on
  genuine spikes/drops in the watched direction only, stay quiet on a
  near-constant series and during cold start, vote stragglers
  cross-sectionally, and fan out to ``apex_anomaly_*`` counters with
  labels preserved.
"""

import json
import logging
import os
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from apex_tpu.models.gpt import GPTConfig, init_params, make_train_step
from apex_tpu.observability import (
    anomaly as anomaly_mod,
    correlation,
    flightrec,
    metrics,
    tracing,
)
from apex_tpu.optimizers import FusedAdam

CFG = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                num_attention_heads=4, max_seq_len=16,
                compute_dtype=jnp.float32, checkpoint_layers=False)


@pytest.fixture(autouse=True)
def _clean_observability_state():
    """Every test starts with no tracer, no recorder, no correlation
    context, and leaves none behind."""
    tracing.disable()
    flightrec.uninstall()
    correlation.clear_step_context()
    yield
    tracing.disable()
    flightrec.uninstall()
    correlation.clear_step_context()


def _data(batch):
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, CFG.vocab_size, size=(batch, 16)))
    return tokens, jnp.roll(tokens, -1, axis=1)


def _mesh(devices8, dp):
    return Mesh(np.array(devices8[:dp]).reshape(dp, 1), ("dp", "tp"))


def _assert_bitwise(tree_a, tree_b):
    for a, b in zip(jax.tree.leaves(tree_a), jax.tree.leaves(tree_b)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------------------- tracer core
class TestTracerCore:
    def test_span_records_name_duration_attrs_thread(self):
        tr = tracing.Tracer()
        with tr.span("train.data_wait", batch=3):
            pass
        (rec,) = tr.spans()
        assert rec["name"] == "train.data_wait"
        assert rec["ph"] == "X"
        assert rec["dur_us"] >= 0
        assert rec["attrs"]["batch"] == 3
        assert rec["tid"] == threading.current_thread().ident
        assert rec["thread"] == threading.current_thread().name

    def test_handle_spelling_and_mid_span_attrs(self):
        tr = tracing.Tracer()
        s = tr.span("serve.verify_step", draft_len=3)
        s.set(emitted=7)
        s.end(accepted=2)
        (rec,) = tr.spans()
        assert rec["attrs"] == {"draft_len": 3, "emitted": 7,
                                "accepted": 2}
        # double-end is a no-op, not a duplicate record
        s.end()
        assert len(tr.spans()) == 1

    def test_exception_exits_span_with_error_attr(self):
        tr = tracing.Tracer()
        with pytest.raises(RuntimeError):
            with tr.span("train.step.dispatch"):
                raise RuntimeError("wedged")
        (rec,) = tr.spans()
        assert rec["attrs"]["error"] == "RuntimeError"
        assert not tr.open_spans()

    def test_ring_bounds_memory_and_counts_drops(self):
        tr = tracing.Tracer(capacity=4)
        for i in range(10):
            with tr.span(f"s{i}"):
                pass
        spans = tr.spans()
        assert len(spans) == 4
        assert [s["name"] for s in spans] == ["s6", "s7", "s8", "s9"]
        assert tr.dropped == 6
        assert tr.started == tr.finished == 10

    def test_open_span_tracked_with_elapsed(self):
        tr = tracing.Tracer()
        s = tr.span("train.step.dispatch", step=7)
        time.sleep(0.01)
        (rec,) = tr.open_spans()
        assert rec["open"] is True
        assert rec["name"] == "train.step.dispatch"
        assert rec["dur_us"] >= 10_000 * 0.5  # monotonic, scheduler slack
        assert not tr.spans()
        s.end()
        assert not tr.open_spans()
        assert len(tr.spans()) == 1

    def test_spans_record_their_thread(self):
        tr = tracing.Tracer()

        def work():
            with tr.span("watchdog.probe"):
                pass

        t = threading.Thread(target=work, name="apex-test-watchdog")
        t.start()
        t.join()
        (rec,) = tr.spans()
        assert rec["thread"] == "apex-test-watchdog"
        assert rec["tid"] != threading.current_thread().ident

    def test_instant_and_retro_emit(self):
        tr = tracing.Tracer()
        tr.instant("train.marker", payload_bytes=1024)
        t0 = time.time() - 0.5
        tr.emit("serve.admission_wait", t0, 0.25, rid=3)
        marker, emitted = tr.spans()
        assert marker["ph"] == "i" and marker["dur_us"] == 0
        assert emitted["ph"] == "X"
        assert emitted["ts"] == pytest.approx(t0)
        assert emitted["dur_us"] == 250_000

    def test_listener_feed_and_listener_errors_swallowed(self):
        tr = tracing.Tracer()
        seen = []
        tr.add_listener(seen.append)
        tr.add_listener(lambda rec: 1 / 0)  # broken observer
        with tr.span("a"):
            pass
        assert [r["name"] for r in seen] == ["a"]

    def test_capacity_validated(self):
        with pytest.raises(ValueError, match="capacity"):
            tracing.Tracer(capacity=0)


class TestSpanCausality:
    """Every span names the span that caused it: ``parent`` is the id
    of what the same thread had open when it started."""

    def test_id_and_parent_under_nesting(self):
        tr = tracing.Tracer()
        with tr.span("outer") as outer:
            with tr.span("first") as first:
                tr.instant("marker")
            with tr.span("second") as second:
                pass
        with tr.span("after") as after:
            pass
        by = {s["name"]: s for s in tr.spans()}
        assert by["outer"]["parent"] is None
        assert by["first"]["parent"] == by["outer"]["id"] == outer.id
        assert by["marker"]["parent"] == first.id
        assert by["second"]["parent"] == outer.id   # a sibling, not a child
        assert by["after"]["parent"] is None
        ids = [outer.id, first.id, by["marker"]["id"], second.id, after.id]
        assert ids == sorted(ids) and len(set(ids)) == 5

    def test_each_thread_has_its_own_stack(self):
        tr = tracing.Tracer()
        inside = threading.Event()
        release = threading.Event()

        def work():
            with tr.span("worker.outer"):
                with tr.span("worker.inner"):
                    inside.set()
                    assert release.wait(timeout=10)

        t = threading.Thread(target=work)
        with tr.span("main.outer"):
            t.start()
            assert inside.wait(timeout=10)
            # the worker has two spans open right now; this thread's
            # next span is still a child of main.outer alone
            with tr.span("main.inner"):
                pass
            release.set()
            t.join(timeout=10)
        assert not t.is_alive()
        by = {s["name"]: s for s in tr.spans()}
        assert by["main.inner"]["parent"] == by["main.outer"]["id"]
        assert by["worker.inner"]["parent"] == by["worker.outer"]["id"]
        assert by["worker.outer"]["parent"] is None
        assert by["main.outer"]["parent"] is None

    def test_retro_emit_names_its_parent_itself(self):
        tr = tracing.Tracer()
        with tr.span("serve.admit") as admit:
            tr.emit("serve.admission_wait", time.time() - 1, 1.0,
                    parent=admit.id, rid=3)
            tr.emit("serve.request", time.time() - 1, 1.0, rid=3)
        wait, whole, _ = tr.spans()
        assert wait["parent"] == admit.id and wait["attrs"] == {"rid": 3}
        # it began before what is open now: the stack is not consulted
        assert whole["parent"] is None
        assert wait["id"] != whole["id"]

    def test_stack_is_empty_after_an_exception_inside_a_span(self):
        tr = tracing.Tracer()
        with pytest.raises(RuntimeError):
            with tr.span("outer"):
                with tr.span("inner"):
                    raise RuntimeError("boom")
        assert tr._open_stack() == []
        with tr.span("next"):
            pass
        assert tr.spans()[-1]["parent"] is None

    def test_a_handle_ended_out_of_order_leaves_the_stack(self):
        tr = tracing.Tracer()
        a = tr.span("a")
        b = tr.span("b")
        a.end()                       # not the top
        assert tr._open_stack() == [b]
        b.end()
        assert tr._open_stack() == []

    def test_exports_carry_id_and_parent(self, tmp_path):
        tr = tracing.Tracer()
        with tr.span("outer") as outer:
            with tr.span("inner"):
                pass
        tr.export_jsonl(tmp_path / "s.jsonl")
        tr.export_chrome(tmp_path / "t.json")
        lines = [json.loads(l)
                 for l in (tmp_path / "s.jsonl").read_text().splitlines()]
        inner = next(l for l in lines if l["span"] == "inner")
        assert inner["parent"] == outer.id and inner["id"] > outer.id
        events = json.loads((tmp_path / "t.json").read_text())["traceEvents"]
        inner = next(e for e in events if e["name"] == "inner")
        assert inner["args"]["parent"] == outer.id
        assert inner["args"]["id"] > outer.id


class TestModuleApi:
    def test_span_without_tracer_is_the_noop_singleton(self):
        import sys

        a = tracing.span("x", attr=1)
        b = tracing.span("y")
        assert a is b is tracing._NOOP
        with a:
            a.set(z=2)
        assert a.elapsed() == 0.0
        assert a.id is None and a.parent is None
        assert not tracing.enabled()
        # the disabled path allocates nothing: no id is drawn and no
        # object outlives a call
        next_id = next(tracing._SPAN_IDS)
        before = sys.getallocatedblocks()
        for _ in range(10_000):
            with tracing.span("serve.decode_step"):
                pass
        assert sys.getallocatedblocks() - before < 100
        assert next(tracing._SPAN_IDS) == next_id + 1

    def test_configure_routes_module_span(self):
        tr = tracing.configure(capacity=16)
        assert tracing.get_tracer() is tr
        with tracing.span("train.data_wait"):
            pass
        tracing.instant("marker")
        assert [s["name"] for s in tr.spans()] == ["train.data_wait",
                                                   "marker"]

    def test_scope_restores_previous_tracer(self):
        outer = tracing.configure()
        with tracing.TracingScope() as inner:
            assert tracing.get_tracer() is inner
            with tracing.span("inner_only"):
                pass
        assert tracing.get_tracer() is outer
        assert not outer.spans()
        assert [s["name"] for s in inner.spans()] == ["inner_only"]

    def test_trace_ids_are_process_unique(self):
        ids = {tracing.new_trace_id() for _ in range(64)}
        assert len(ids) == 64
        assert all(i.startswith(f"{os.getpid():x}-") for i in ids)

    def test_correlation_captured_at_span_start(self):
        tr = tracing.configure()
        correlation.set_step_context(run_id="r1", step=7)
        s = tracing.span("train.step.dispatch")
        correlation.set_step_context(step=8)  # the loop moved on
        s.end()
        (rec,) = tr.spans()
        assert rec["attrs"]["run_id"] == "r1"
        assert rec["attrs"]["step"] == 7


# -------------------------------------------------------------- exporters
class TestExporters:
    def _traced(self, tmp_path):
        tr = tracing.configure()
        correlation.set_step_context(run_id="exp", step=3)
        with tr.span("train.step.dispatch", dispatch=True):
            pass
        tr.span("train.data_wait")  # left OPEN deliberately
        return tr

    def test_chrome_export_is_perfetto_loadable(self, tmp_path):
        tr = self._traced(tmp_path)
        path = tmp_path / "trace.json"
        n = tr.export_chrome(path)
        doc = json.loads(path.read_text())
        assert doc["schema"] == "apex_tpu_trace_v1"
        events = doc["traceEvents"]
        assert len(events) == n
        by_name = {e["name"]: e for e in events}
        d = by_name["train.step.dispatch"]
        # the Chrome trace-event contract: phase X, µs timestamps,
        # pid/tid ints, attrs under args
        assert d["ph"] == "X" and d["dur"] >= 0
        assert isinstance(d["ts"], int) and d["ts"] > 1e15  # epoch µs
        assert d["args"]["run_id"] == "exp" and d["args"]["step"] == 3
        assert by_name["train.data_wait"]["args"]["open"] is True
        meta = [e for e in events if e["ph"] == "M"]
        assert meta and all(e["name"] == "thread_name" for e in meta)
        # atomic publish: no staging files left behind
        assert [p.name for p in tmp_path.iterdir()] == ["trace.json"]

    def test_jsonl_export_carries_sidecar_contract(self, tmp_path):
        tr = self._traced(tmp_path)
        path = tmp_path / "spans.jsonl"
        n = tr.export_jsonl(path)
        lines = [json.loads(l) for l in path.read_text().splitlines()]
        assert len(lines) == n == 2
        done, open_ = lines
        assert done["span"] == "train.step.dispatch"
        assert done["run_id"] == "exp" and done["step"] == 3
        assert {"ts", "dur_us", "tid", "thread", "rank"} <= set(done)
        assert done["open"] is False and open_["open"] is True


# ----------------------------------------------------------- TracedStep
class TestTracedStep:
    def test_wraps_dispatch_in_a_span_only_when_tracing(self):
        calls = []

        def fn(x, y):
            calls.append((x, y))
            return x + y

        wrapped = tracing.TracedStep(fn, name="train.step.dispatch")
        assert wrapped(1, 2) == 3  # tracing off: plain delegation
        with tracing.TracingScope() as tr:
            assert wrapped(3, 4) == 7
        assert calls == [(1, 2), (3, 4)]
        (rec,) = tr.spans()
        assert rec["name"] == "train.step.dispatch"
        assert rec["attrs"]["dispatch"] is True

    def test_the_wrapped_callable_has_one_call_site(self):
        """Tracing on or off, the step is called from the same line: the
        line is in every Mosaic kernel's bytecode, and with it in the
        compile cache's key."""
        import sys

        lines = []
        step = tracing.TracedStep(
            lambda: lines.append(sys._getframe(1).f_lineno))
        step()
        with tracing.TracingScope():
            step()
        assert len(lines) == 2 and lines[0] == lines[1]

    def test_delegates_attributes_to_the_wrapped_callable(self):
        class FakeStep:
            def __call__(self, x):
                return x

            def lower(self, *a):
                return "lowering"

            def _cache_size(self):
                return 1

        w = tracing.TracedStep(FakeStep())
        assert w.lower() == "lowering"
        assert w._cache_size() == 1


# ------------------------------------------------------------ parity band
class TestTracingParity:
    """Tracing on (TracedStep under an active tracer) vs off: bitwise
    loss/params on the real train step.  The variants of the ISSUE 14
    acceptance: replicated+clip, ZeRO+clip, hierarchical int8."""

    def _run(self, make_step, n=3):
        params = init_params(CFG, jax.random.PRNGKey(0))
        step, state, (tokens, targets) = make_step(params)
        losses = []
        for _ in range(n):
            params, state, loss = step(params, state, tokens, targets)
            losses.append(float(loss))
        return params, state, losses

    def _pair(self, make_step):
        with tracing.TracingScope() as tr:
            traced = self._run(
                lambda p: self._with_traced_wrapper(make_step, p))
        plain = self._run(make_step)
        _assert_bitwise(traced[0], plain[0])
        _assert_bitwise(traced[1], plain[1])
        assert traced[2] == plain[2]
        dispatch = [s for s in tr.spans()
                    if s["name"] == "train.step.dispatch"]
        assert len(dispatch) == 3  # the spans really recorded
        return tr

    @staticmethod
    def _with_traced_wrapper(make_step, params):
        step, state, data = make_step(params)
        return tracing.TracedStep(step, name="train.step.dispatch"), \
            state, data

    def test_replicated_clip(self, devices8):
        def make(params):
            opt = FusedAdam(lr=1e-2)
            step = make_train_step(CFG, opt, _mesh(devices8, 2),
                                   clip_grad_norm=1.0)
            return step, opt.init(params), _data(2)

        self._pair(make)

    def test_zero_clip(self, devices8):
        from apex_tpu.contrib.optimizers import DistributedFusedAdam

        def make(params):
            opt = DistributedFusedAdam(lr=1e-2, axis_name="dp")
            state = opt.init(params, world_size=2)
            step = make_train_step(CFG, opt, _mesh(devices8, 2),
                                   clip_grad_norm=1.0)
            return step, state, _data(2)

        self._pair(make)

    def test_hier_int8(self, devices8):
        from apex_tpu.contrib.optimizers import DistributedFusedAdam

        mesh = Mesh(np.array(devices8[:4]).reshape(2, 2, 1),
                    ("dp_out", "dp_in", "tp"))

        def make(params):
            opt = DistributedFusedAdam(lr=1e-2,
                                       dp_axes=("dp_out", "dp_in"),
                                       grad_sync_dtype="int8")
            state = opt.init(params, world_size=4,
                             axis_sizes={"dp_out": 2, "dp_in": 2,
                                         "tp": 1})
            step = make_train_step(CFG, opt, mesh,
                                   dp_axis=("dp_out", "dp_in"))
            return step, state, _data(4)

        self._pair(make)


# -------------------------------------------------------- flight recorder
class TestFlightRecorder:
    def test_rings_are_bounded(self):
        rec = flightrec.FlightRecorder(capacity=3, events_capacity=2,
                                       stats_capacity=2)
        for i in range(6):
            rec.record_span({"name": f"s{i}", "ph": "X", "dur_us": 1})
            rec.record_event(f"e{i}", {"i": i})
            rec.record_stats(i, {"loss_mean": float(i)})
        snap = rec.snapshot()
        assert [s["name"] for s in snap["spans"]] == ["s3", "s4", "s5"]
        assert [e["event"] for e in snap["events"]] == ["e4", "e5"]
        assert [s["step"] for s in snap["stats_windows"]] == [4, 5]

    def test_dump_and_load_roundtrip(self, tmp_path):
        correlation.set_step_context(run_id="fr", step=9)
        rec = flightrec.FlightRecorder(tmp_path, run_id="fr")
        rec.record_span({"name": "train.step.dispatch", "ph": "X",
                         "dur_us": 5})
        path = rec.dump("wedge", wedged_step=9)
        assert path is not None and rec.dumped == [path]
        loaded = flightrec.load_dump(path)
        assert loaded["reason"] == "wedge"
        assert loaded["wedged_step"] == 9
        assert loaded["run_id"] == "fr" and loaded["step"] == 9
        assert loaded["spans"][0]["name"] == "train.step.dispatch"

    def test_dump_includes_tracers_open_span(self, tmp_path):
        """The wedged dispatch never finishes — the dump must name it
        anyway (the forensics headline)."""
        tr = tracing.configure()
        rec = flightrec.FlightRecorder(tmp_path).attach(tr)
        correlation.set_step_context(run_id="w", step=4)
        wedged = tracing.span("train.step.dispatch", dispatch=True)
        path = rec.dump("wedge", wedged_step=4)
        loaded = flightrec.load_dump(path)
        (open_span,) = loaded["open_spans"]
        assert open_span["name"] == "train.step.dispatch"
        assert open_span["open"] is True
        assert open_span["attrs"]["step"] == 4
        wedged.end()

    def test_attach_feeds_finished_spans(self):
        tr = tracing.configure()
        rec = flightrec.FlightRecorder().attach(tr)
        with tracing.span("serve.decode_step"):
            pass
        assert [s["name"] for s in rec.snapshot()["spans"]] \
            == ["serve.decode_step"]

    def test_checkpoint_republishes_atomically(self, tmp_path):
        rec = flightrec.FlightRecorder(tmp_path)
        rec.record_event("a", {})
        p1 = rec.checkpoint()
        rec.record_event("b", {})
        p2 = rec.checkpoint()
        assert p1 == p2  # one rolling file, republished
        events = [e["event"]
                  for e in flightrec.load_dump(p1)["events"]]
        assert events == ["a", "b"]
        assert flightrec.FlightRecorder().checkpoint() is None

    def test_log_structured_feeds_installed_recorder(self):
        from apex_tpu.utils.logging import get_logger, log_structured

        rec = flightrec.install(flightrec.FlightRecorder())
        correlation.set_step_context(run_id="lg", step=2)
        log_structured(get_logger("apex_tpu.test"), logging.INFO,
                       "checkpoint.saved", step_dir="/x/step_2")
        (ev,) = rec.snapshot()["events"]
        assert ev["event"] == "checkpoint.saved"
        assert ev["step_dir"] == "/x/step_2"
        assert ev["run_id"] == "lg" and ev["step"] == 2
        flightrec.uninstall()
        log_structured(get_logger("apex_tpu.test"), logging.INFO,
                       "after.uninstall")
        assert len(rec.snapshot()["events"]) == 1

    def test_dump_active_is_a_noop_without_a_recorder(self):
        assert flightrec.dump_active("wedge") is None

    def test_dump_never_raises(self, tmp_path, monkeypatch):
        rec = flightrec.FlightRecorder(tmp_path)
        monkeypatch.setattr(rec, "snapshot",
                            lambda *a, **k: 1 / 0)
        assert rec.dump("wedge") is None  # reported, not raised


class TestDumpReadSide:
    def _good_dump(self, tmp_path, **extra):
        rec = flightrec.FlightRecorder(tmp_path)
        return rec.dump("wedge", **extra)

    def test_load_dump_rejects_torn_bytes(self, tmp_path):
        p = tmp_path / "flightrec_dump_1_1.json"
        p.write_text('{"schema": "apex_tpu_flightrec_v1", "spans": [')
        with pytest.raises(ValueError, match="torn/partial"):
            flightrec.load_dump(p)

    def test_load_dump_rejects_wrong_schema(self, tmp_path):
        p = tmp_path / "flightrec_dump_1_1.json"
        p.write_text('{"schema": "something_else"}')
        with pytest.raises(ValueError, match="schema"):
            flightrec.load_dump(p)

    def test_latest_dump_skips_torn_files_loudly(self, tmp_path):
        good = self._good_dump(tmp_path, wedged_step=5)
        torn = tmp_path / "flightrec_dump_9999999999999_1.json"
        torn.write_text('{"schema": "apex_tpu_flightrec_v1", "ev')
        os.utime(torn, (time.time() + 60, time.time() + 60))  # newest

        from apex_tpu.utils.logging import get_logger

        records = []
        handler = logging.Handler()
        handler.emit = records.append
        logger = get_logger("apex_tpu.observability")
        logger.addHandler(handler)
        try:
            path, rec = flightrec.latest_dump(tmp_path)
        finally:
            logger.removeHandler(handler)
        assert path == good and rec["wedged_step"] == 5
        loud = [r.getMessage() for r in records
                if "torn_dump_skipped" in r.getMessage()]
        assert loud and torn.name in loud[0]  # loud, and names the file

    def test_latest_dump_none_cases(self, tmp_path):
        assert flightrec.latest_dump(tmp_path) is None
        assert flightrec.latest_dump_path(tmp_path / "missing") is None
        assert flightrec.latest_dump_path(None) is None

    def test_latest_dump_path_finds_newest(self, tmp_path):
        clock = iter(np.arange(1.0, 10.0, 0.5))
        rec = flightrec.FlightRecorder(tmp_path,
                                       time_fn=lambda: float(next(clock)))
        first = rec.dump("wedge")
        second = rec.dump("preemption")
        os.utime(first, (1, 1))
        os.utime(second, (2, 2))
        assert flightrec.latest_dump_path(tmp_path) == second


# ----------------------------------------------------------- dump triggers
class TestDumpTriggers:
    """The chaos matrix: every library exit path leaves a dump."""

    def test_step_guard_abort_dumps_before_the_raise(self, tmp_path):
        from apex_tpu.resilience import BadStepBudgetExceeded, StepGuard
        from apex_tpu.resilience.step_guard import GuardState

        flightrec.install(flightrec.FlightRecorder(tmp_path))
        guard = StepGuard(max_consecutive_bad=2)
        bad = GuardState(step=jnp.int32(10), consecutive_bad=jnp.int32(2),
                         total_skipped=jnp.int32(3))
        with pytest.raises(BadStepBudgetExceeded):
            guard.check(bad)
        path, rec = flightrec.latest_dump(tmp_path)
        assert rec["reason"] == "step_guard_abort"
        assert rec["consecutive_bad"] == 2
        assert rec["guard_step"] == 10

    def test_preemption_notice_dumps(self, tmp_path):
        from apex_tpu.resilience import PreemptionHandler

        flightrec.install(flightrec.FlightRecorder(tmp_path))
        h = PreemptionHandler(signals=())
        h.simulate("chaos preemption")
        _, rec = flightrec.latest_dump(tmp_path)
        assert rec["reason"] == "preemption"
        assert rec["preempt_reason"] == "chaos preemption"
        # the notice dumps ONCE (the flag is latched)
        h.simulate("again")
        assert len([p for p in os.listdir(tmp_path)
                    if p.startswith("flightrec_dump_")]) == 1

    def test_watchdog_wedge_dumps_with_the_wedged_step(self, tmp_path):
        """rc-75 forensics in-process: the watchdog fire path (via the
        on_fire test seam, which replaces only the final os._exit)
        dumps a recording whose OPEN span is the wedged dispatch with
        the right (run_id, step)."""
        from apex_tpu.resilience import StepWatchdog

        tr = tracing.configure()
        flightrec.install(
            flightrec.FlightRecorder(tmp_path, run_id="wdg").attach(tr))
        correlation.set_step_context(run_id="wdg", step=6)
        fired = []
        wedged = tracing.span("train.step.dispatch", dispatch=True)
        with StepWatchdog(0.15, poll_sec=0.02,
                          on_fire=fired.append) as wd:
            wd.beat(6)
            deadline = time.monotonic() + 5.0
            while not fired and time.monotonic() < deadline:
                time.sleep(0.02)
        wedged.end()
        assert fired, "watchdog never fired"
        info = fired[0]
        assert info["step"] == 6
        assert info["flight_dump"] is not None
        rec = flightrec.load_dump(info["flight_dump"])
        assert rec["reason"] == "wedge"
        assert rec["wedged_step"] == 6
        assert rec["run_id"] == "wdg" and rec["step"] == 6
        (open_span,) = rec["open_spans"]
        assert open_span["name"] == "train.step.dispatch"
        assert open_span["attrs"]["step"] == 6


# ---------------------------------------------------------------- anomaly
class TestRobustZscore:
    def test_median_mad_math(self):
        z, med, mad = anomaly_mod.robust_zscore(
            10.0, [1.0, 2.0, 3.0, 4.0, 100.0], min_rel_spread=0.0)
        assert med == 3.0 and mad == 1.0
        assert z == pytest.approx((10.0 - 3.0)
                                  / (anomaly_mod.MAD_TO_SIGMA * 1.0))

    def test_rel_spread_floor_quiets_constant_series(self):
        # microsecond jitter on a ~1.0s series: the floor dominates
        z, _, _ = anomaly_mod.robust_zscore(
            1.000004, [1.000001, 1.000002, 1.000001, 1.000003])
        assert abs(z) < 1.0


class TestRollingMadDetector:
    def test_spike_alarms_high_direction(self):
        det = anomaly_mod.RollingMadDetector(window=32, threshold=4.0,
                                             min_points=8)
        rng = np.random.RandomState(0)
        for v in 1.0 + 0.01 * rng.randn(20):
            assert det.update(v) is None
        hit = det.update(3.0)
        assert hit is not None and hit["zscore"] > 4.0
        assert det.alerts == 1

    def test_cold_start_is_quiet(self):
        det = anomaly_mod.RollingMadDetector(min_points=16)
        for _ in range(15):
            assert det.update(1.0) is None
        assert det.update(100.0) is None  # still < min_points history

    def test_direction_low_alarms_on_drops_only(self):
        det = anomaly_mod.RollingMadDetector(window=32, min_points=8,
                                             direction="low")
        rng = np.random.RandomState(1)
        for v in 100.0 + rng.randn(20):
            det.update(v)
        assert det.update(300.0) is None   # spike: not watched
        assert det.update(10.0) is not None  # drop: alarm

    def test_outlier_does_not_mask_itself(self):
        """The candidate is scored against the window EXCLUDING it."""
        det = anomaly_mod.RollingMadDetector(window=8, min_points=4,
                                             threshold=4.0)
        for v in (1.0, 1.01, 0.99, 1.02, 1.0):
            det.update(v)
        assert det.update(50.0) is not None

    def test_knob_validation(self):
        with pytest.raises(ValueError, match="window"):
            anomaly_mod.RollingMadDetector(window=1)
        with pytest.raises(ValueError, match="direction"):
            anomaly_mod.RollingMadDetector(direction="sideways")
        with pytest.raises(ValueError, match="min_points"):
            anomaly_mod.RollingMadDetector(min_points=1)


class TestAnomalyMonitor:
    def _ramp(self, mon, kind, n=24, base=1.0, **labels):
        rng = np.random.RandomState(7)
        for v in base + 0.01 * base * rng.randn(n):
            mon.observe(kind, v, **labels)

    def test_detection_increments_counter_with_labels(self):
        with metrics.MetricsScope() as reg:
            mon = anomaly_mod.AnomalyMonitor(min_points=8)
            self._ramp(mon, "ttft", lane="interactive")
            alert = mon.observe("ttft", 30.0, lane="interactive")
            assert alert is not None and alert["lane"] == "interactive"
            c = reg.counter("apex_anomaly_ttft_total",
                            labelnames=("lane",))
            assert c.value(lane="interactive") == 1.0

    def test_series_keyed_per_label_set(self):
        """A best-effort-lane regression must not poison the
        interactive lane's window (and vice versa)."""
        mon = anomaly_mod.AnomalyMonitor(min_points=8)
        self._ramp(mon, "ttft", base=1.0, lane="interactive")
        self._ramp(mon, "ttft", base=60.0, lane="best_effort")
        # 50s is normal for best_effort, anomalous for interactive
        assert mon.observe("ttft", 50.0, lane="best_effort") is None
        assert mon.observe("ttft", 50.0, lane="interactive") is not None

    def test_goodput_kind_watches_drops(self):
        mon = anomaly_mod.AnomalyMonitor(min_points=8)
        self._ramp(mon, "tokens_per_sec", base=1000.0)
        assert mon.observe("tokens_per_sec", 1500.0) is None
        assert mon.observe("tokens_per_sec", 100.0) is not None

    def test_wedge_is_an_unconditional_alert(self):
        with metrics.MetricsScope() as reg:
            mon = anomaly_mod.AnomalyMonitor()
            rec = mon.wedge(300.0, step=17)
            assert rec["wedge"] is True and rec["step"] == 17
            assert reg.counter("apex_anomaly_step_time_total").value() \
                == 1.0
        assert mon.counts() == {"step_time": 1}

    def test_straggler_vote(self):
        mon = anomaly_mod.AnomalyMonitor(threshold=4.0)
        per_rank = {0: 1.0, 1: 1.01, 2: 0.99, 3: 1.02, 4: 5.0}
        alerts = mon.check_stragglers(per_rank)
        assert [a["rank"] for a in alerts] == ["4"]
        assert alerts[0]["series"] == "rank_step_time"
        # two ranks: no majority to deviate from
        assert mon.check_stragglers({0: 1.0, 1: 9.0}) == []

    def test_span_listener_routes_durations(self):
        mon = anomaly_mod.AnomalyMonitor(min_points=8)
        tr = tracing.Tracer()
        tr.add_listener(mon.span_listener({
            "serve.decode_step": "inter_token",
            "zero_sync.*": "hop_sync_time",
        }))
        for _ in range(12):
            tr.emit("serve.decode_step", time.time(), 0.01)
            tr.emit("zero_sync.bucket0.hop_dp", time.time(), 0.02)
        tr.emit("serve.decode_step", time.time(), 5.0)     # spike
        tr.emit("zero_sync.bucket0.hop_dp", time.time(), 9.0)
        tr.emit("unmapped.span", time.time(), 99.0)        # ignored
        counts = mon.counts()
        assert counts == {"inter_token": 1, "hop_sync_time": 1}
        (hop_alert,) = [a for a in mon.alerts
                        if a["kind"] == "hop_sync_time"]
        assert hop_alert["span"] == "zero_sync.bucket0.hop_dp"

    def test_mixed_label_shapes_still_count_in_the_registry(self):
        """A kind fed alerts with two label shapes must not lose the
        second shape's counter increments: the registry pins labelnames
        at first use and the best-effort helper swallows the clash, so
        _alert conforms later shapes to the first-seen schema (and the
        span_listener feed emits ONE stable shape to begin with)."""
        with metrics.MetricsScope() as reg:
            mon = anomaly_mod.AnomalyMonitor(min_points=8)
            tr = tracing.Tracer()
            tr.add_listener(mon.span_listener({"serve.*": "inter_token"}))
            for _ in range(12):  # laneless spans build the baseline
                tr.emit("serve.decode_step", time.time(), 0.01)
                tr.emit("serve.prefill", time.time(), 0.01,
                        lane="interactive")
            tr.emit("serve.decode_step", time.time(), 5.0)   # laneless
            tr.emit("serve.prefill", time.time(), 9.0,       # laned
                    lane="interactive")
            assert mon.counts() == {"inter_token": 2}
            ctr = reg.counter("apex_anomaly_inter_token_total",
                              labelnames=("lane", "span"))
            total = sum(v for _, _, v in ctr.samples())
            assert total == 2  # neither increment swallowed
            # direct misuse conforms too instead of losing the count
            mon._alert("custom", {"a": "1"}, {"value": 1.0})
            mon._alert("custom", {"b": "2"}, {"value": 1.0})
            c2 = reg.counter("apex_anomaly_custom_total",
                             labelnames=("a",))
            assert sum(v for _, _, v in c2.samples()) == 2

    def test_alert_lands_in_flight_recorder(self):
        rec = flightrec.install(flightrec.FlightRecorder())
        mon = anomaly_mod.AnomalyMonitor(min_points=8)
        self._ramp(mon, "step_time")
        mon.observe("step_time", 50.0)
        events = [e for e in rec.snapshot()["events"]
                  if e["event"] == "anomaly.detected"]
        assert len(events) == 1 and events[0]["kind"] == "step_time"

    def test_counts_by_lane(self):
        mon = anomaly_mod.AnomalyMonitor(min_points=8)
        self._ramp(mon, "ttft", lane="interactive")
        mon.observe("ttft", 40.0, lane="interactive")
        assert mon.counts_by("lane") == {"ttft": {"interactive": 1}}


class TestAnomalyPersistence:
    def _persisted(self, tmp_path):
        mon = anomaly_mod.AnomalyMonitor(min_points=8)
        rng = np.random.RandomState(3)
        for v in 1.0 + 0.01 * rng.randn(16):
            mon.observe("step_time", v)
        mon.observe("step_time", 99.0)
        return mon.persist(tmp_path)

    def test_persist_and_recent_alert_count(self, tmp_path):
        path = self._persisted(tmp_path)
        doc = json.loads(open(path).read())
        assert doc["schema"] == "apex_tpu_anomaly_v1"
        assert doc["counts"] == {"step_time": 1}
        assert anomaly_mod.recent_alert_count(tmp_path) == 1
        assert anomaly_mod.recent_alert_count(None) == 0
        assert anomaly_mod.recent_alert_count(tmp_path / "missing") == 0

    def test_recent_alert_count_age_gate_and_torn_files(self, tmp_path):
        self._persisted(tmp_path)
        (tmp_path / "anomaly_torn.json").write_text('{"schema": "apex')
        assert anomaly_mod.recent_alert_count(tmp_path) == 1
        assert anomaly_mod.recent_alert_count(
            tmp_path, max_age_sec=10.0,
            now=time.time() + 3600.0) == 0


# ------------------------------------------------- supervisor consumption
class TestSupervisorForensics:
    """The supervisor attaches the newest dump to restart/quarantine
    records and lengthens backoff on fresh anomaly alerts."""

    class _MaxJitter:
        def uniform(self, a, b):
            return b

    class _FakeChild:
        def __init__(self, rc):
            self.rc = rc

        def wait(self, timeout=None):
            return self.rc

        def terminate(self):
            pass

        def kill(self):
            pass

    def _supervisor(self, tmp_path, rcs, **kw):
        from apex_tpu.resilience.supervisor import Supervisor

        it = iter(rcs)
        return Supervisor(
            ["prog"], max_restarts=8, metrics_dir=str(tmp_path),
            spawn_fn=lambda argv: self._FakeChild(next(it)),
            sleep_fn=lambda s: None, time_fn=lambda: 0.0,
            rng=self._MaxJitter(), backoff_base=1.0, backoff_cap=64.0,
            progress_fn=lambda: 0, **kw)

    def test_restart_record_attaches_dump_path(self, tmp_path):
        dump = flightrec.FlightRecorder(
            os.path.join(tmp_path, "flightrec")).dump(
                "wedge", wedged_step=3)
        sup = self._supervisor(tmp_path, [75, 0])
        assert sup.run() == 0
        assert sup.flight_dumps == [dump]

    def test_restart_record_none_without_dumps(self, tmp_path):
        sup = self._supervisor(tmp_path, [137, 0])
        assert sup.run() == 0
        assert sup.flight_dumps == [None]

    def test_anomaly_alerts_lengthen_backoff_once_per_batch(self,
                                                           tmp_path):
        """FRESH alerts (appearing after run start) double the next
        backoff exactly once; the second failure with no new alerts
        backs off normally."""
        counts = iter([0, 2, 2])  # baseline read, then per-failure
        plain = self._supervisor(tmp_path, [75, 75, 0],
                                 anomaly_fn=lambda: 0)
        assert plain.run() == 0
        loud = self._supervisor(tmp_path, [75, 75, 0],
                                anomaly_fn=lambda: next(counts))
        assert loud.run() == 0
        assert loud.backoffs[0] == pytest.approx(2 * plain.backoffs[0])
        assert loud.backoffs[1] == pytest.approx(plain.backoffs[1])

    def test_anomaly_watermark_tracks_aged_out_summaries_down(
            self, tmp_path):
        """`recent_alert_count` DROPS as summary files age out of its
        window; the watermark must follow it down, or a high-alert
        attempt more than an hour ago would silently eat the next batch
        of fresh alerts (the healthy-for-an-hour server case)."""
        counts = iter([0, 5, 0, 3])  # baseline; ramp; aged out; fresh
        sup = self._supervisor(tmp_path, [75, 75, 75, 0],
                               crash_loop_threshold=8,
                               anomaly_fn=lambda: next(counts))
        assert sup.run() == 0
        plain = self._supervisor(tmp_path, [75, 75, 75, 0],
                                 crash_loop_threshold=8,
                                 anomaly_fn=lambda: 0)
        assert plain.run() == 0
        assert sup.backoffs[0] == pytest.approx(2 * plain.backoffs[0])
        assert sup.backoffs[1] == pytest.approx(plain.backoffs[1])
        # 3 fresh alerts AFTER the old summary aged out (count fell
        # 5 -> 0 -> 3): still "new regressions", still lengthened
        assert sup.backoffs[2] == pytest.approx(2 * plain.backoffs[2])

    def test_stale_anomaly_summaries_do_not_lengthen(self, tmp_path):
        """Summaries a PREVIOUS run left under the same metrics dir are
        the baseline, not fresh evidence: a new supervisor's first
        backoff stays plain."""
        mon = anomaly_mod.AnomalyMonitor(min_points=8)
        rng = np.random.RandomState(5)
        for v in 1.0 + 0.01 * rng.randn(16):
            mon.observe("step_time", v)
        mon.observe("step_time", 77.0)
        mon.persist(tmp_path)  # run A's leftovers
        plain = self._supervisor(tmp_path, [75, 0],
                                 anomaly_fn=lambda: 0)
        assert plain.run() == 0
        stale = self._supervisor(tmp_path, [75, 0])  # default reader
        assert stale.run() == 0
        assert stale.backoffs == plain.backoffs


# ------------------------------------------------ scheduler trace joining
class TestServeTraceJoin:
    """The ISSUE 14 scheduler fix: a TTFT histogram outlier joins to
    its request's spans through the shared trace_id exemplar."""

    def _completions(self, tr):
        return _submit_and_drain(_tiny_scheduler(max_batch=2, num_pages=40),
                                 n=2, plen=6, new=3)

    def test_trace_id_joins_exemplar_to_spans(self):
        with metrics.MetricsScope() as reg, \
                tracing.TracingScope() as tr:
            completions = self._completions(tr)
        assert len(completions) == 2
        ids = {c.rid: c.trace_id for c in completions}
        assert all(ids.values()) and len(set(ids.values())) == 2
        # the histogram sample is no longer anonymous: its exemplar
        # carries the trace id...
        hist = reg.histogram("apex_serve_ttft_seconds",
                             labelnames=("lane",))
        exemplars = hist.drain_exemplars()
        assert {ex["trace_id"] for _, ex in exemplars} \
            == set(ids.values())
        # ...and the same id is on the request's spans
        by_id = {}
        for s in tr.spans():
            tid = s.get("attrs", {}).get("trace_id")
            if tid is not None:
                by_id.setdefault(tid, set()).add(s["name"])
        for tid in ids.values():
            assert {"serve.admission_wait", "serve.prefill",
                    "serve.request"} <= by_id[tid]
        # ...and the batch-level decode/verify spans name every
        # resident request, so the exemplar also joins to the EXACT
        # steps that served it, not just the whole-lifetime span
        decode = [s for s in tr.spans()
                  if s["name"] in ("serve.decode_step",
                                   "serve.verify_step")
                  and s["attrs"].get("active", 0) > 0]
        assert decode
        for s in decode:
            carried = s["attrs"].get("trace_ids")
            assert carried and len(carried) == s["attrs"]["active"]
            assert set(carried) <= set(ids.values())
        for tid in ids.values():  # every request decoded at least once
            assert any(tid in s["attrs"]["trace_ids"] for s in decode)

    def test_admission_wait_is_caused_by_its_admission_pass(self):
        """``serve.admission_wait`` is caused by the admission pass that
        ended it, and carries why its request had blocked."""
        with tracing.TracingScope() as tr:
            self._completions(tr)
        spans = tr.spans()
        admits = {s["id"] for s in spans if s["name"] == "serve.admit"}
        waits = [s for s in spans if s["name"] == "serve.admission_wait"]
        assert len(waits) == 2
        for w in waits:
            assert w["parent"] in admits
            assert w["attrs"]["blocked_on"] is None   # two slots, two requests

    def test_window_max_exemplar_survives_ring_eviction(self):
        """serve_gpt.py drains exemplars exactly once, at the end of
        the run: a mid-run p99 outlier must still be present after
        hundreds of ordinary samples, or the join the exemplar exists
        for is lost to recency eviction."""
        with metrics.MetricsScope() as reg:
            hist = reg.histogram("apex_serve_ttft_seconds",
                                 labelnames=("lane",))
            hist.observe(9.9, exemplar={"trace_id": "outlier"},
                         lane="interactive")
            for i in range(200):  # ordinary traffic after the spike
                hist.observe(0.01, exemplar={"trace_id": f"t{i}"},
                             lane="interactive")
            drained = hist.drain_exemplars()
            assert len(drained) == metrics.Histogram.MAX_EXEMPLARS
            by_id = {ex["trace_id"]: ex for _, ex in drained}
            assert by_id["outlier"]["value"] == 9.9
            # recency is otherwise preserved (the most recent samples)
            assert f"t199" in by_id and f"t198" in by_id

    def test_exemplars_ride_the_jsonl_snapshot_once(self, tmp_path):
        with metrics.MetricsScope() as reg:
            reg.histogram("apex_serve_ttft_seconds",
                          labelnames=("lane",)).observe(
                0.5, exemplar={"trace_id": "t-1", "rid": 7},
                lane="interactive")
            path = tmp_path / "metrics.jsonl"
            reg.snapshot_jsonl(path)
            reg.snapshot_jsonl(path)  # drained: not re-emitted
        lines = [json.loads(l) for l in path.read_text().splitlines()]
        ex = [l for l in lines if l["type"] == "exemplar"]
        assert len(ex) == 1
        assert ex[0]["metric"] == "apex_serve_ttft_seconds_exemplar"
        assert ex[0]["trace_id"] == "t-1" and ex[0]["rid"] == 7
        assert ex[0]["labels"] == {"lane": "interactive"}


# ------------------------------------------------ scheduler span tiling
LEAVES = ("serve.admit", "serve.prefill", "serve.prefill_chunk",
          "serve.decode_step", "serve.verify_step", "serve.emit")

# ------------------------------------------------------- compile spans
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
COMPILE_NAMES = ("compile.trace", "compile.lower", "compile.backend")


def _compiles(spans, fun=None):
    return [s for s in spans if s["name"] in COMPILE_NAMES
            and (fun is None or fun in s["attrs"]["fun_name"])]


def _our_listeners():
    """What ``jax.monitoring`` holds of the tracer's."""
    from jax._src import monitoring

    held = (monitoring.get_event_listeners()
            + monitoring.get_scalar_listeners()
            + monitoring.get_event_time_span_listeners()
            + monitoring.get_event_duration_listeners())
    return [fn for fn in held
            if getattr(fn, "__self__", None) is tracing._COMPILES]


def _inside(child, parent, slack_s=1e-3):
    end = lambda s: s["ts"] + s["dur_us"] / 1e6
    return (parent["ts"] - slack_s <= child["ts"]
            and end(child) <= end(parent) + slack_s)


class TestCompileSpans:
    """ISSUE 49: every trace, lowering and backend compile is a child
    span of the call that caused it, with JAX's own endpoints; whole
    programs only; nothing is registered while no tracer is."""

    def test_a_first_call_leaves_three_children_of_its_span(self):
        def cause_a(x):
            return jnp.tanh(x) * 3.0

        x = jnp.ones((4,))
        with tracing.TracingScope() as tr:
            with tracing.span("outer"):
                with tracing.span("x", bucket=4) as sp:
                    jax.jit(cause_a)(x).block_until_ready()
        spans = tr.spans()
        mine = _compiles(spans, "cause_a")
        assert [s["name"] for s in mine] == list(COMPILE_NAMES)
        caller = next(s for s in spans if s["name"] == "x")
        for s in mine:
            assert s["parent"] == sp.id == caller["id"]
            assert s["ph"] == "X" and _inside(s, caller)
            assert s["tid"] == caller["tid"]
        # one after the other, as JAX made them
        for a, b in zip(mine, mine[1:]):
            assert a["ts"] + a["dur_us"] / 1e6 <= b["ts"] + 1e-3
        backend = mine[-1]["attrs"]
        assert sorted(backend) == ["cache", "fun_name"]
        assert backend["cache"] in ("hit", "miss", "off")
        assert "cache" not in mine[0]["attrs"]

    def test_a_second_call_compiles_nothing_and_a_new_shape_recompiles(self):
        def cause_b(x):
            return jnp.tanh(x) - 1.0

        f = jax.jit(cause_b)
        small, large = jnp.ones((4,)), jnp.ones((8,))
        with tracing.TracingScope() as tr:
            with tracing.span("first"):
                f(small).block_until_ready()
            with tracing.span("second") as second:
                f(small).block_until_ready()
            with tracing.span("third") as third:
                f(large).block_until_ready()
        mine = _compiles(tr.spans(), "cause_b")
        assert not [s for s in mine if s["parent"] == second.id]
        # after warm-up any compile child of a span is a recompile, and
        # the span it hangs under names the call that caused it
        again = [s for s in mine if s["parent"] == third.id]
        assert [s["name"] for s in again] == list(COMPILE_NAMES)
        assert len(mine) == 6

    def test_a_compile_outside_every_span_has_no_parent(self):
        def cause_c(x):
            return x + 2.0

        x = jnp.ones((4,))
        with tracing.TracingScope() as tr:
            jax.jit(cause_c)(x).block_until_ready()
        mine = _compiles(tr.spans(), "cause_c")
        assert len(mine) == 3 and all(s["parent"] is None for s in mine)

    def test_hundreds_of_jitted_primitives_are_one_program(self):
        """JAX announces a trace for every jitted ``jnp`` function a
        program's trace calls; only the program reaches the ring."""
        announced = []

        def count(event, value, **kw):
            if event == TRACE_EVENT:
                announced.append(kw.get("fun_name"))

        def many(x):
            for i in range(150):
                x = jnp.where(x > i, jnp.sinc(x), jnp.logaddexp(x, 1.0 * i))
            return x

        x = jnp.ones((4,))
        jax.monitoring.register_scalar_listener(count)
        try:
            with tracing.TracingScope(capacity=64) as tr:
                jax.jit(many)(x).block_until_ready()
        finally:
            jax.monitoring.unregister_scalar_listener(count)
        assert len(announced) > 100
        found = _compiles(tr.spans())
        assert [s["name"] for s in found] == list(COMPILE_NAMES)
        assert tr.dropped == 0

    def test_nothing_is_registered_without_a_tracer(self):
        def cause_d(x):
            return x * 5.0

        x = jnp.ones((4,))
        assert not _our_listeners()
        jax.jit(cause_d)(x).block_until_ready()       # records nowhere
        with tracing.TracingScope() as outer:
            assert len(_our_listeners()) == 3
            with tracing.TracingScope() as inner:
                assert len(_our_listeners()) == 3
            assert len(_our_listeners()) == 3
            assert not inner.spans()
        assert not _our_listeners()
        assert not _compiles(outer.spans(), "cause_d")
        tracing.configure()
        tracing.configure()                    # a second tracer, one set
        assert len(_our_listeners()) == 3
        tracing.disable()
        assert not _our_listeners()
        tracing.disable()

    @pytest.mark.parametrize("events, verdict", [
        (("cache_hits",), "hit"), (("cache_misses",), "miss"), ((), "off")])
    def test_backend_span_says_what_the_cache_did(self, events, verdict):
        """The listener on JAX's own events, fired by hand: the cache's
        verdict is the one since the last backend span."""
        with tracing.TracingScope() as tr:
            with tracing.span("caller") as sp:
                t0 = time.time()
                for name in events:
                    jax.monitoring.record_event(
                        f"/jax/compilation_cache/{name}")
                jax.monitoring.record_scalar(BACKEND_EVENT, t0,
                                             fun_name="jit(fired)")
                jax.monitoring.record_event_time_span(
                    BACKEND_EVENT, t0, t0 + 0.25, fun_name="jit(fired)")
                jax.monitoring.record_scalar(BACKEND_EVENT, t0 + 0.25,
                                             fun_name="jit(fired)")
                jax.monitoring.record_event_time_span(
                    BACKEND_EVENT, t0 + 0.25, t0 + 0.5,
                    fun_name="jit(fired)")
        first, second = _compiles(tr.spans(), "fired")
        assert first["attrs"]["cache"] == verdict
        assert second["attrs"]["cache"] == "off"      # used up
        assert first["parent"] == sp.id
        assert first["dur_us"] == pytest.approx(250000, abs=2)
        assert first["ts"] == t0

    def test_a_span_opened_after_the_compile_began_is_not_its_parent(self):
        """The parent is the innermost span that was open when the
        event STARTED."""
        with tracing.TracingScope() as tr:
            with tracing.span("caller") as caller:
                t0 = time.time()
                jax.monitoring.record_scalar(TRACE_EVENT, t0, fun_name="g")
                time.sleep(0.002)
                with tracing.span("late"):
                    jax.monitoring.record_event_time_span(
                        TRACE_EVENT, t0, time.time(), fun_name="g")
        (g,) = _compiles(tr.spans(), "g")
        assert g["parent"] == caller.id

    def test_an_end_without_its_start_is_recorded_once(self):
        """Listeners installed in the middle of a compile."""
        with tracing.TracingScope() as tr:
            t0 = time.time()
            jax.monitoring.record_event_time_span(
                TRACE_EVENT, t0, t0 + 0.1, fun_name="half")
            jax.monitoring.record_event_time_span(
                "/jax/some/other_duration", t0, t0 + 0.1)
        assert [s["name"] for s in tr.spans()] == ["compile.trace"]

    def test_a_start_the_last_tracer_left_open_hides_nothing_of_the_next(self):
        """A tracer that leaves while a thread is inside a compile saw
        the start and not the end; the next tracer starts from depth 0."""
        t0 = time.time()
        with tracing.TracingScope():
            jax.monitoring.record_scalar(TRACE_EVENT, t0, fun_name="cut")
        with tracing.TracingScope() as tr:
            jax.monitoring.record_scalar(TRACE_EVENT, t0, fun_name="whole")
            jax.monitoring.record_event_time_span(
                TRACE_EVENT, t0, t0 + 0.1, fun_name="whole")
        assert [s["attrs"]["fun_name"] for s in _compiles(tr.spans())] \
            == ["whole"]

    def test_an_error_in_the_listener_is_counted_not_raised(
            self, monkeypatch):
        def broken(self, *a, **kw):
            raise RuntimeError("a bug in the recorder")

        with tracing.TracingScope() as tr:
            assert tr.compile_errors == 0
            monkeypatch.setattr(tracing.Tracer, "_compiled", broken)
            t0 = time.time()
            jax.monitoring.record_event_time_span(
                TRACE_EVENT, t0, t0 + 0.1, fun_name="lost")
        assert tr.compile_errors == 1 and not _compiles(tr.spans())

    def test_a_compile_on_another_thread_is_that_threads(self):
        def cause_e(x):
            return x - 7.0

        x = jnp.ones((4,))

        def work():
            with tracing.span("worker.call"):
                jax.jit(cause_e)(x).block_until_ready()

        with tracing.TracingScope() as tr:
            with tracing.span("main.call"):
                t = threading.Thread(target=work, name="compiler")
                t.start()
                t.join(60)
                assert not t.is_alive()
        spans = tr.spans()
        worker = next(s for s in spans if s["name"] == "worker.call")
        mine = _compiles(spans, "cause_e")
        assert len(mine) == 3
        assert all(s["parent"] == worker["id"]
                   and s["thread"] == "compiler" for s in mine)

    def test_a_served_first_prefill_reads_as_its_compiles_and_the_rest(self):
        """Every compile span of a traced serve run is a child of a
        device call's span (or of none) and lies inside it; the first
        prefill of the bucket has all three."""
        done, spans, stats = _serve("slots")
        assert len(done) == 5
        by_id = {s["id"]: s for s in spans}
        found = _compiles(spans)
        assert found
        callers = ("serve.prefill", "serve.decode_step",
                   "serve.prepare_params", "serve.verify_step",
                   "serve.prefill_chunk")
        for s in found:
            if s["parent"] is not None:
                parent = by_id[s["parent"]]
                # the admission pass itself owns one small program: the
                # first token written into the device's vector, after
                # its prefill's span has closed
                assert parent["name"] in callers or (
                    parent["name"] == "serve.admit"
                    and "_set_token" in s["attrs"]["fun_name"]), parent
                assert _inside(s, parent)
        prefills = sorted((s for s in spans if s["name"] == "serve.prefill"),
                          key=lambda s: s["ts"])
        children = lambda p: sorted(
            s["name"] for s in found if s["parent"] == p["id"]
            and "prefill" in s["attrs"]["fun_name"])
        assert children(prefills[0]) == sorted(COMPILE_NAMES)
        assert prefills[0]["attrs"]["padded_tokens"] == 16
        assert all(not children(p) for p in prefills[1:])
        first = sum(s["dur_us"] for s in found
                    if s["parent"] == prefills[0]["id"])
        assert 0 < first <= prefills[0]["dur_us"]

    def test_trace_ids_are_built_where_the_resident_set_changes(
            self, monkeypatch):
        """``serve.decode_step`` carries the resident ids without
        scanning the slots every step: a scan after a change of the
        resident set, and none at all with tracing off."""
        from apex_tpu.inference import ContinuousBatchingScheduler

        calls = []
        scan = ContinuousBatchingScheduler._active_trace_ids

        def counted(self):
            calls.append(self.stats["decode_steps"])
            return scan(self)

        monkeypatch.setattr(ContinuousBatchingScheduler,
                            "_active_trace_ids", counted)
        with tracing.TracingScope() as tr:
            sched = _tiny_scheduler(max_batch=2, num_pages=40)
            done = _submit_and_drain(sched, n=3, plen=6, new=12)
        assert len(done) == 3
        steps = [s for s in tr.spans() if s["name"] == "serve.decode_step"
                 and s["attrs"]["active"] > 0]
        assert len(steps) >= 20
        # at most one scan an admission and one an eviction, however
        # many steps lie between them
        assert 0 < len(calls) <= 2 * 3
        ids = {c.trace_id for c in done}
        for s in steps:
            assert len(s["attrs"]["trace_ids"]) == s["attrs"]["active"]
            assert set(s["attrs"]["trace_ids"]) <= ids
        assert sched._trace_ids is None        # the last eviction's mark
        assert sched._resident_trace_ids() == ()
        del calls[:]
        sched = _tiny_scheduler(max_batch=2, num_pages=40)   # no tracer
        assert len(_submit_and_drain(sched, n=3, plen=6, new=12)) == 3
        assert not calls and sched._trace_ids is None


#: name -> scheduler settings and requests (count, prompt length, new
#: tokens).  ``slots``: more requests than slots; ``pages``: slots to
#: spare but a pool that holds one request (3 pages of 4) at a time;
#: ``chunked_spec``: the chunked-prefill and verify-step paths.
SERVERS = {
    "slots": (dict(max_batch=2, num_pages=40), (5, 6, 4)),
    "pages": (dict(max_batch=3, num_pages=5), (3, 6, 3)),
    "chunked_spec": (dict(max_batch=2, num_pages=40, prefill_chunk=4,
                          draft_len=2), (4, 10, 6)),
}


def _tiny_scheduler(num_pages, **knobs):
    """The suite's tiny server: a 2-layer model, pages of 4, greedy."""
    from apex_tpu.inference import (
        ContinuousBatchingScheduler, DecodeConfig, KVCacheConfig,
    )

    cfg = GPTConfig(vocab_size=61, hidden_size=32, num_layers=2,
                    num_attention_heads=4, max_seq_len=128,
                    position_embedding_type="rope",
                    compute_dtype=jnp.float32, checkpoint_layers=False)
    params = init_params(cfg, jax.random.PRNGKey(0))
    dcfg = DecodeConfig(
        cache=KVCacheConfig(num_pages=num_pages, page_size=4,
                            pages_per_seq=16, dtype=jnp.float32),
        max_prompt_len=16, temperature=0.0, attn_impl="xla",
        sample_impl="xla", sample_dot_dtype=jnp.float32, **knobs)
    return ContinuousBatchingScheduler(params, cfg, dcfg)


def _submit_and_drain(sched, n, plen, new):
    from apex_tpu.inference import Request

    rng = np.random.RandomState(0)
    for rid in range(n):
        sched.submit(Request(
            rid=rid, prompt=rng.randint(0, 61, size=plen).tolist(),
            max_new_tokens=new))
    return sched.run_until_drained()


def _serve(name):
    """One traced run of the tiny server: (completions, spans, stats)."""
    knobs, (n, plen, new) = SERVERS[name]
    with tracing.TracingScope() as tr:
        sched = _tiny_scheduler(**knobs)
        done = _submit_and_drain(sched, n, plen, new)
    return done, tr.spans(), dict(sched.stats)


class TestServeSpans:
    """The scheduler's leaf spans tile ``step()``, and a request's
    record holds the decomposition of its own first token."""

    @pytest.fixture(scope="class", params=sorted(SERVERS))
    def served(self, request):
        return (request.param,) + _serve(request.param)

    def test_leaf_spans_never_overlap_except_parent_and_child(self, served):
        _, _, spans, _ = served
        leaves = sorted((s for s in spans if s["name"] in LEAVES),
                        key=lambda s: (s["ts"], -s["dur_us"]))
        assert {s["name"] for s in leaves} >= {"serve.admit", "serve.emit"}
        assert len({s["tid"] for s in leaves}) == 1
        by_id = {s["id"]: s for s in leaves}

        def ancestors(s):
            while s["parent"] in by_id:
                s = by_id[s["parent"]]
                yield s["id"]

        for a, b in zip(leaves, leaves[1:]):
            a_end = a["ts"] + a["dur_us"] / 1e6
            if b["ts"] < a_end - 2e-6:          # dur_us is truncated
                assert a["id"] in set(ancestors(b)), (
                    f"{b['name']} starts inside {a['name']}, which is "
                    f"not its ancestor")

    def test_prefill_is_caused_by_an_admission_pass(self, served):
        name, _, spans, stats = served
        admits = {s["id"]: s for s in spans if s["name"] == "serve.admit"}
        prefills = [s for s in spans if s["name"] == "serve.prefill"]
        if name == "chunked_spec":      # chunks run beside admission
            assert not prefills
            chunks = [s for s in spans if s["name"] == "serve.prefill_chunk"]
            assert all(c["parent"] is None for c in chunks)
            assert sum(c["attrs"]["last"] for c in chunks) \
                == stats["prefills"]
            return
        assert len(prefills) == stats["prefills"]
        for p in prefills:
            assert p["parent"] in admits
            assert 0 <= p["attrs"]["dispatch_us"] <= p["dur_us"]
        assert sum(a["attrs"]["admitted"] for a in admits.values()) \
            == stats["admitted"]
        assert all(a["attrs"]["queued"] >= 1 for a in admits.values())

    def test_emit_follows_every_step_and_counts_its_tokens(self, served):
        name, done, spans, stats = served
        step = ("serve.verify_step" if name == "chunked_spec"
                else "serve.decode_step")
        steps = [s for s in spans if s["name"] == step]
        emits = [s for s in spans if s["name"] == "serve.emit"]
        assert len(emits) == stats["decode_steps"]
        if name == "chunked_spec":      # synchronous: a span a step
            assert len(steps) == len(emits)
        else:
            # the plain loop keeps one step in flight: a span launches a
            # step, reads one back, or (in_flight) does both, and every
            # step is launched once and read once
            over = stats["decode_overlapped"]
            assert len(steps) == 2 * stats["decode_steps"] - over
            assert sum(s["attrs"]["in_flight"] for s in steps) == over > 0
            assert stats["decode_settles"] == 0
        # every token but each request's first comes out of a step
        assert sum(e["attrs"]["tokens"] for e in emits) \
            == sum(len(c.tokens) - 1 for c in done)
        assert sum(e["attrs"]["evicted"] for e in emits) \
            <= stats["evicted"]
        step_ids = {s["id"] for s in steps}
        for e in emits:     # a child of the verify span, after a decode
            assert (e["parent"] in step_ids) == (name == "chunked_spec")
        # prefills are charged to the step whose gap they lengthened
        assert sum(s["attrs"]["prefills_before"] for s in steps) \
            <= stats["prefills"]
        assert steps[0]["attrs"]["prefills_before"] >= 1

    def test_request_span_takes_the_first_token_apart(self, served):
        _, done, spans, _ = served
        reqs = {s["attrs"]["rid"]: s for s in spans
                if s["name"] == "serve.request"}
        assert sorted(reqs) == sorted(c.rid for c in done)
        for c in done:
            at = reqs[c.rid]["attrs"]
            assert {"queue_s", "prefill_s", "ttft_s", "blocked_on"} \
                <= set(at)
            assert at["queue_s"] + at["prefill_s"] \
                == pytest.approx(at["ttft_s"], abs=1e-3)
            assert at["ttft_s"] == pytest.approx(
                c.token_times[0] - c.submit_time, abs=1e-5)
            assert at["queue_s"] == pytest.approx(
                c.admit_time - c.submit_time, abs=1e-5)
            assert reqs[c.rid]["dur_us"] / 1e6 == pytest.approx(
                c.finish_time - c.submit_time, abs=1e-4)

    def test_completion_times_are_ordered(self, served):
        _, done, _, _ = served
        for c in done:
            assert c.submit_time <= c.admit_time <= c.token_times[0] \
                <= c.finish_time
        # every request was submitted before the first step: the later
        # ones queued, and their submit time says so
        assert max(c.admit_time - c.submit_time for c in done) \
            > min(c.admit_time - c.submit_time for c in done)

    def test_blocked_on_names_what_the_head_lacked(self, served):
        name, done, spans, stats = served
        reason = "pages" if name == "pages" else "slot"
        other = "slot" if reason == "pages" else "pages"
        assert stats["admit_blocked_" + reason] >= 1
        assert stats["admit_blocked_" + other] == 0
        reqs = {s["attrs"]["rid"]: s["attrs"] for s in spans
                if s["name"] == "serve.request"}
        waits = {s["attrs"]["rid"]: s["attrs"] for s in spans
                 if s["name"] == "serve.admission_wait"}
        first = min(c.rid for c in done)
        assert reqs[first]["blocked_on"] is None    # admitted at once
        blocked = [rid for rid, at in reqs.items() if at["blocked_on"]]
        assert blocked and all(reqs[r]["blocked_on"] == reason
                               and waits[r]["blocked_on"] == reason
                               for r in blocked)
        passes = [s["attrs"]["blocked_on"] for s in spans
                  if s["name"] == "serve.admit"]
        assert passes.count(reason) == stats["admit_blocked_" + reason]
        assert set(passes) <= {reason, None}


def test_a_steps_ops_are_summed_by_name_inside_the_step_program():
    """``benchmarks/cell_step_ops.by_name`` over a loaded trace: the
    most frequent program is the step, its first and last executions
    (cut by the trace's edges) are left out, an op's SELF time counts
    (a fusion less the kernel inside it), names lose their numbering,
    and what runs inside another program (a prefill) is not the
    step's."""
    import importlib.util
    import pathlib

    path = pathlib.Path(__file__).resolve().parents[1] \
        / "benchmarks" / "cell_step_ops.py"
    spec = importlib.util.spec_from_file_location("cell_step_ops", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    ms = 1_000_000
    steps = [["jit_step(1)", i * 10 * ms, 8 * ms] for i in range(5)]
    ops = []
    for i in range(5):
        t = i * 10 * ms
        ops += [["%fusion.12", t, 4 * ms], ["%apex_kv_write.3", t + ms, ms],
                ["%fusion.7", t + 5 * ms, 2 * ms]]
    ops.append(["%apex_flash_fwd.1", 48 * ms + 500, ms])    # a prefill's
    loaded = {"devices": {"/device:TPU:0": ops},
              "modules": {"/device:TPU:0": steps + [
                  ["jit_prefill(2)", 48 * ms, 2 * ms]]},
              "sync_ns": None}
    table = mod.by_name(loaded)
    assert table["program"] == "jit_step(1)" and table["steps"] == 3
    assert table["step_ms"] == 8.0
    assert dict(table["ops_ms"]) == {"fusion": 5.0, "apex_kv_write": 1.0}
    assert table["programs"]["jit_prefill(2)"] == [1, 2.0]
