"""apex_tpu.observability: metrics registry, device-side StepStats
telemetry, async fetch, goodput accounting, serving metrics.

The load-bearing bands:

- **Parity**: telemetry-on vs telemetry-off train steps produce
  BITWISE-identical loss and params in fp32 — stats are observers,
  never participants — including the ZeRO + int8-sync engine and the
  StepGuard/chaos composition (the collective/host-transfer side of
  the same contract is pinned in tests/test_lowered_invariants.py).
- **Goodput closure**: the report's fractions sum to exactly 1 over
  the run's wall clock, with a wedged session's tail and the
  inter-session gap attributed to their causes.
"""

import json
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from apex_tpu.models.gpt import GPTConfig, init_params, make_train_step
from apex_tpu.observability import correlation, goodput, metrics, stepstats
from apex_tpu.optimizers import FusedAdam


# ------------------------------------------------------------------ metrics
class TestMetricsRegistry:
    def test_counter_gauge_histogram_round_trip(self):
        reg = metrics.MetricsRegistry()
        c = reg.counter("t_total", "help", ("k",))
        c.inc(k="a")
        c.inc(2.5, k="a")
        c.inc(k="b")
        assert c.value(k="a") == 3.5 and c.value(k="b") == 1.0
        g = reg.gauge("t_gauge")
        g.set(7.0)
        g.set(3.0)
        assert g.value() == 3.0
        h = reg.histogram("t_seconds", buckets=(0.1, 1.0))
        for v in (0.05, 0.5, 5.0):
            h.observe(v)
        samples = {(n, tuple(sorted(l.items()))): v
                   for n, l, v in h.samples()}
        assert samples[("t_seconds_bucket", (("le", "0.1"),))] == 1
        assert samples[("t_seconds_bucket", (("le", "1"),))] == 2
        assert samples[("t_seconds_bucket", (("le", "+Inf"),))] == 3
        assert samples[("t_seconds_count", ())] == 3
        assert samples[("t_seconds_sum", ())] == pytest.approx(5.55)

    def test_counter_cannot_decrease_and_kind_clash_is_loud(self):
        reg = metrics.MetricsRegistry()
        c = reg.counter("x_total")
        with pytest.raises(ValueError, match="cannot decrease"):
            c.inc(-1)
        assert reg.counter("x_total") is c  # get-or-create
        with pytest.raises(ValueError, match="already registered"):
            reg.gauge("x_total")
        with pytest.raises(ValueError, match="do not match"):
            reg.counter("y_total", labelnames=("a",)).inc(b=1)

    def test_prometheus_text_format(self):
        reg = metrics.MetricsRegistry()
        reg.counter("apex_t_total", "things", ("kind",)).inc(kind="x")
        reg.histogram("apex_l_seconds", buckets=(1.0,)).observe(0.5)
        txt = reg.prometheus_text()
        assert "# HELP apex_t_total things" in txt
        assert "# TYPE apex_t_total counter" in txt
        assert '# TYPE apex_l_seconds histogram' in txt
        assert 'apex_t_total{kind="x",rank="0"} 1' in txt
        assert 'apex_l_seconds_bucket{le="+Inf",rank="0"} 1' in txt
        assert txt.endswith("\n")

    def test_snapshot_jsonl_carries_correlation(self, tmp_path):
        reg = metrics.MetricsRegistry()
        reg.gauge("apex_t_g").set(2.0)
        correlation.set_step_context(run_id="r1", step=17)
        try:
            p = tmp_path / "m.jsonl"
            n = reg.snapshot_jsonl(p, extra_field="x")
            assert n == 1
            rec = json.loads(p.read_text())
            assert rec["metric"] == "apex_t_g" and rec["value"] == 2.0
            assert rec["run_id"] == "r1" and rec["step"] == 17
            assert rec["extra_field"] == "x" and "ts" in rec
        finally:
            correlation.clear_step_context()

    def test_module_helpers_are_best_effort(self):
        """The retrofit helpers must never alter the caller's control
        flow: a registry clash (here: the name is already a gauge) logs
        once and returns instead of raising into the fallback/watchdog/
        drain path that recorded through them."""
        with metrics.MetricsScope() as reg:
            reg.gauge("apex_clash")            # pre-register as gauge
            metrics.inc("apex_clash")          # kind clash: no raise
            metrics.observe("apex_clash", 1.0)  # no raise either
            # direct registry use stays STRICT
            with pytest.raises(ValueError, match="already registered"):
                reg.counter("apex_clash")

    def test_histogram_bucket_clash_is_loud(self):
        reg = metrics.MetricsRegistry()
        reg.histogram("apex_h_seconds", buckets=(1.0, 2.0))
        assert reg.histogram("apex_h_seconds",
                             buckets=(2.0, 1.0)) is not None  # same set
        with pytest.raises(ValueError, match="different bounds"):
            reg.histogram("apex_h_seconds", buckets=(0.5,))

    def test_scope_isolates_module_helpers(self):
        with metrics.MetricsScope() as reg:
            metrics.inc("apex_scoped_total", kind="a")
            assert metrics.get_metrics() is reg
            assert reg.counter(
                "apex_scoped_total", labelnames=("kind",)).value(
                    kind="a") == 1
        # outside the scope, the default registry did not see it
        assert metrics.get_metrics() is not reg

    def test_log_structured_merges_step_context(self):
        import logging

        from apex_tpu.utils.logging import get_logger, log_structured

        logger = get_logger("apex_tpu.t")
        records = []
        handler = logging.Handler()
        handler.emit = records.append  # the apex logger never propagates
        logger.addHandler(handler)
        correlation.set_step_context(run_id="corr", step=5)
        try:
            log_structured(logger, logging.WARNING, "evt", a=1)
        finally:
            correlation.clear_step_context()
            logger.removeHandler(handler)
        payload = json.loads(records[-1].getMessage().split(" ", 1)[1])
        assert payload == {"a": 1, "run_id": "corr", "step": 5}

    @pytest.mark.parametrize("batch", [1, 7], ids=["observe",
                                                    "observe_many"])
    def test_scrape_is_a_consistent_snapshot_under_hammer(self, batch):
        """Two-thread hammer for the torn-scrape race: a writer thread
        (the watchdog shape) observes a CONSTANT value into a
        histogram and bumps a counter while the main thread scrapes.
        Every observation lands v=1.0 in the (0.5, 1.5) bucket, so a
        consistent snapshot must satisfy bucket{le=1.5} == count and
        sum == count EXACTLY — the pre-fix lazy expansion (children
        copied under the lock, buckets/sum/count read outside it)
        tears mid-observe and breaks the invariant."""
        import re
        import sys
        import threading

        reg = metrics.MetricsRegistry()
        h = reg.histogram("apex_hammer_seconds", buckets=(0.5, 1.5))
        c = reg.counter("apex_hammer_total")
        stop = threading.Event()
        # shrink the GIL switch interval so the writer interleaves
        # into any unlocked window (the pre-fix tear reproduces in
        # ~20k scrapes at 1µs; at the 5ms default it hides for hours)
        prev_switch = sys.getswitchinterval()
        sys.setswitchinterval(1e-6)

        def writer():
            while not stop.is_set():
                if batch == 1:
                    h.observe(1.0)
                else:
                    h.observe_many([1.0] * batch)
                c.inc()
                reg.gauge(f"apex_g_{threading.get_ident() % 7}").set(1)

        t = threading.Thread(target=writer, name="hammer-writer")
        t.start()
        try:
            for _ in range(300):
                txt = reg.prometheus_text()

                def val(pat, txt=txt):
                    m = re.search(pat + r"\S* (\S+)", txt)
                    return None if m is None else float(m.group(1))

                count = val(r"apex_hammer_seconds_count")
                if count is None:
                    continue  # scrape ran before the first observe
                assert count % batch == 0, txt
                # torn scrape: the cumulative buckets, the +Inf
                # bucket, _sum and _count disagree with each other
                assert val(
                    r'apex_hammer_seconds_bucket\{le="1\.5"') == count, txt
                assert val(
                    r'apex_hammer_seconds_bucket\{le="\+Inf"') == count, txt
                assert val(r"apex_hammer_seconds_sum") \
                    == pytest.approx(count), txt
        finally:
            stop.set()
            t.join()
            sys.setswitchinterval(prev_switch)

    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_observe_many_is_observe_a_value(self, seed):
        """Bucket counts, ``sum``, ``count`` and the Prometheus text of
        one ``observe_many`` a batch EQUAL those of ``observe`` a value
        in the same order (values on a bucket's bound, beyond the last,
        and a label a series); the batch's one exemplar carries its
        largest value."""
        rng = np.random.RandomState(seed)
        bounds = metrics.DEFAULT_BUCKETS
        batches = [list(rng.lognormal(-5.0, 2.0, size=n))
                   + [bounds[int(rng.randint(len(bounds)))], 1e4]
                   for n in rng.randint(1, 130, size=9)] + [[]]
        regs = metrics.MetricsRegistry(), metrics.MetricsRegistry()
        one, many = (r.histogram("apex_serve_inter_token_seconds",
                                 "previous token -> this token",
                                 ("lane",)) for r in regs)
        for k, batch in enumerate(batches):
            lane = ("interactive", "best_effort")[k % 2]
            for v in batch:
                one.observe(v, lane=lane)
            many.observe_many(batch, exemplar={"rid": k}, lane=lane)
        assert list(one.samples()) == list(many.samples())
        assert regs[0].prometheus_text() == regs[1].prometheus_text()
        assert not one.drain_exemplars()
        got = {ex["rid"]: ex["value"] for _, ex in many.drain_exemplars()}
        assert got == {k: max(b) for k, b in enumerate(batches) if b}
        # the module helper, on the current registry
        with metrics.MetricsScope() as reg:
            metrics.observe_many("apex_x_seconds", batches[0], lane="a")
            h = reg.histogram("apex_x_seconds", labelnames=("lane",))
            assert dict((n, v) for n, _, v in h.samples())[
                "apex_x_seconds_count"] == len(batches[0])

    def test_nvtx_range_suffix(self):
        from apex_tpu.utils.profiler import nvtx_range

        correlation.set_step_context(run_id="r-2", step=3)
        try:
            assert correlation.span_suffix() == ".run_r-2.s3"
            with nvtx_range("fwd"):   # must not raise with the suffix
                pass
        finally:
            correlation.clear_step_context()
        assert correlation.span_suffix() == ""


# ---------------------------------------------------------------- stepstats
class TestStepStats:
    def test_accumulate_window_math(self):
        tel = stepstats.StepTelemetry(norms=False)
        s = tel.init()
        s = tel.accumulate(s, loss=jnp.float32(2.0),
                           grad_norm=jnp.float32(3.0),
                           finite=jnp.bool_(True),
                           loss_scale=jnp.float32(8.0))
        s = tel.accumulate(s, loss=jnp.float32(4.0),
                           grad_norm=jnp.float32(5.0),
                           finite=jnp.bool_(False),
                           loss_scale=jnp.float32(4.0))
        assert int(s.steps) == 2 and int(s.notfinite) == 1
        assert float(s.loss_sum) == 6.0 and float(s.loss_last) == 4.0
        assert float(s.grad_norm_sum) == 8.0
        assert float(s.grad_norm_last) == 5.0
        assert float(s.loss_scale) == 4.0

    def test_accumulate_absent_optionals(self):
        tel = stepstats.StepTelemetry(norms=False)
        s = tel.accumulate(tel.init(), loss=jnp.float32(1.0))
        assert int(s.steps) == 1 and int(s.notfinite) == 0
        assert math.isnan(float(s.grad_norm_last))
        assert math.isnan(float(s.loss_scale))

    def test_param_update_norms(self):
        tel = stepstats.StepTelemetry(norms=True)
        old = {"a": jnp.asarray([3.0, 4.0])}
        new = {"a": jnp.asarray([3.0, 4.0]) + 1.0}
        s = tel.accumulate(tel.init(), loss=jnp.float32(0.0),
                           new_params=new, old_params=old)
        assert float(s.param_norm) == pytest.approx(
            float(jnp.sqrt(jnp.sum(jnp.square(new["a"])))))
        assert float(s.update_norm) == pytest.approx(np.sqrt(2.0))

    def test_init_buffers_are_distinct(self):
        # shared zero buffers would double-donate through the step
        s = stepstats.StepTelemetry().init()
        leaves = jax.tree.leaves(s)
        f32 = [x for x in leaves if x.dtype == jnp.float32]
        assert len({x.unsafe_buffer_pointer() for x in f32}) == len(f32)

    def test_summary_and_emit(self):
        tel = stepstats.StepTelemetry(norms=False)
        s = tel.accumulate(tel.init(), loss=jnp.float32(2.0),
                           grad_norm=jnp.float32(1.0),
                           finite=jnp.bool_(True))
        tree = jax.tree.map(np.asarray, s._asdict())
        reg = metrics.MetricsRegistry()
        summ = stepstats.StepTelemetry.emit(reg, tree)
        assert summ["loss_mean"] == 2.0 and summ["bad_steps"] == 0
        assert reg.gauge("apex_train_loss").value() == 2.0
        assert reg.counter("apex_train_steps_total").value() == 1

    def test_capture_seam(self):
        assert not stepstats.capturing()
        stepstats.offer("x", 1)  # no-op outside capture
        with stepstats.capture() as cap:
            assert stepstats.capturing()
            stepstats.offer("grad_norm", 7)
            with stepstats.capture() as inner:
                stepstats.offer("grad_norm", 9)
            assert inner == {"grad_norm": 9}
        assert cap == {"grad_norm": 7}
        assert not stepstats.capturing()


class TestAsyncFetcher:
    def test_fifo_harvest_and_flush(self):
        f = stepstats.AsyncFetcher()
        f.put("loss", 0, {"loss": jnp.float32(1.0)})
        f.put("loss", 1, {"loss": jnp.float32(2.0)})
        got = f.ready()
        assert [(k, s) for k, s, _ in got] == [("loss", 0), ("loss", 1)]
        assert isinstance(got[0][2]["loss"], np.ndarray)
        assert float(got[1][2]["loss"]) == 2.0
        f.put("stats", 2, {"v": jnp.int32(3)})
        rest = f.flush()
        assert len(f) == 0 and rest[0][:2] == ("stats", 2)

    def test_non_jax_leaves_pass_through(self):
        f = stepstats.AsyncFetcher()
        f.put("x", 0, {"a": 1.5})
        (_, _, tree), = f.ready()
        assert float(tree["a"]) == 1.5

    def test_concurrent_flush_never_drops_or_doubles(self):
        """The exit-path race (APX114's shape, fixed by the internal
        lock): the loop thread harvests with ready() while an exit
        path (preemption drain, watchdog) calls flush() concurrently.
        Every window must be harvested by EXACTLY ONE caller, and
        each caller's batch must stay FIFO by step."""
        import threading

        for _ in range(20):
            f = stepstats.AsyncFetcher()
            n = 200
            for i in range(n):
                f.put("w", i, {"v": float(i)})
            batches = {}
            barrier = threading.Barrier(2)

            def harvest(name, fn):
                barrier.wait()
                out = []
                for _ in range(50):
                    out.extend(fn())
                batches[name] = out

            t1 = threading.Thread(
                target=harvest, args=("loop", f.ready))
            t2 = threading.Thread(
                target=harvest, args=("exit", f.flush))
            t1.start(); t2.start(); t1.join(); t2.join()
            leftover = f.flush()
            steps_loop = [s for _, s, _ in batches["loop"]]
            steps_exit = [s for _, s, _ in batches["exit"]]
            steps_left = [s for _, s, _ in leftover]
            # exactly-once: the three disjoint batches cover 0..n-1
            all_steps = sorted(steps_loop + steps_exit + steps_left)
            assert all_steps == list(range(n))
            # per-caller FIFO
            assert steps_loop == sorted(steps_loop)
            assert steps_exit == sorted(steps_exit)


# ------------------------------------------------------------------ parity
CFG = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                num_attention_heads=4, max_seq_len=16,
                compute_dtype=jnp.float32, checkpoint_layers=False)


def _data(batch):
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, CFG.vocab_size, size=(batch, 16)))
    return tokens, jnp.roll(tokens, -1, axis=1)


def _mesh(devices8, dp):
    return Mesh(np.array(devices8[:dp]).reshape(dp, 1), ("dp", "tp"))


def _assert_bitwise(tree_a, tree_b):
    for a, b in zip(jax.tree.leaves(tree_a), jax.tree.leaves(tree_b)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestTelemetryParity:
    """Telemetry on/off → bitwise-identical loss/params in fp32."""

    def _run_pair(self, build, run_on, run_off, steps=3):
        losses_on, losses_off = [], []
        st_on = build(True)
        st_off = build(False)
        for i in range(steps):
            losses_on.append(run_on(st_on, i))
            losses_off.append(run_off(st_off, i))
        return st_on, st_off, losses_on, losses_off

    def test_plain_step_with_clip(self, devices8):
        mesh = _mesh(devices8, 2)
        tokens, targets = _data(2)
        tel = stepstats.StepTelemetry()

        def make(with_tel):
            params = init_params(CFG, jax.random.PRNGKey(0))
            opt = FusedAdam(lr=1e-2)
            state = opt.init(params)
            step = make_train_step(
                CFG, opt, mesh, clip_grad_norm=1.0,
                telemetry=tel if with_tel else None)
            return {"p": params, "s": state, "step": step,
                    "stats": tel.init() if with_tel else None}

        a, b = make(True), make(False)
        for i in range(3):
            a["p"], a["s"], a["stats"], loss_a = a["step"](
                a["p"], a["s"], a["stats"], tokens, targets)
            b["p"], b["s"], loss_b = b["step"](
                b["p"], b["s"], tokens, targets)
            assert float(loss_a) == float(loss_b)
        _assert_bitwise(a["p"], b["p"])
        _assert_bitwise(a["s"], b["s"])
        # and the window really observed: 3 steps, clip's global norm
        assert int(a["stats"].steps) == 3
        assert np.isfinite(float(a["stats"].grad_norm_last))

    def test_scaled_guarded_chaos_composition(self, devices8):
        """fp16-style scaler + StepGuard + chaos NaN injection: the
        poisoned step is skipped identically on both sides and the
        telemetry counts it."""
        from apex_tpu.amp import DynamicLossScaler
        from apex_tpu.resilience import ChaosMonkey, ChaosPlan, StepGuard

        mesh = _mesh(devices8, 2)
        tokens, targets = _data(2)
        tel = stepstats.StepTelemetry()
        guard = StepGuard(max_consecutive_bad=5)
        scaler = DynamicLossScaler(init_scale=2.0 ** 4)

        def make(with_tel):
            params = init_params(CFG, jax.random.PRNGKey(0))
            opt = FusedAdam(lr=1e-2)
            state = opt.init(params)
            chaos = ChaosMonkey(ChaosPlan.make(nan_grad_steps=(1,)))
            step = make_train_step(
                CFG, opt, mesh, loss_scaler=scaler, step_guard=guard,
                chaos=chaos, telemetry=tel if with_tel else None)
            return {"p": params, "s": state, "sc": scaler.init(),
                    "g": guard.init(), "step": step,
                    "stats": tel.init() if with_tel else None}

        a, b = make(True), make(False)
        for i in range(3):
            (a["p"], a["s"], a["sc"], a["g"], a["stats"], loss_a) = \
                a["step"](a["p"], a["s"], a["sc"], a["g"], a["stats"],
                          tokens, targets)
            (b["p"], b["s"], b["sc"], b["g"], loss_b) = \
                b["step"](b["p"], b["s"], b["sc"], b["g"], tokens, targets)
        _assert_bitwise(a["p"], b["p"])
        _assert_bitwise([a["sc"].loss_scale, a["g"].total_skipped],
                        [b["sc"].loss_scale, b["g"].total_skipped])
        assert int(a["stats"].notfinite) == 1  # the injected NaN step
        assert float(a["stats"].loss_scale) == float(a["sc"].loss_scale)

    def test_zero_int8_sync(self, devices8):
        from apex_tpu.contrib.optimizers import DistributedFusedAdam

        mesh = _mesh(devices8, 2)
        tokens, targets = _data(2)
        tel = stepstats.StepTelemetry()

        def make(with_tel):
            params = init_params(CFG, jax.random.PRNGKey(0))
            opt = DistributedFusedAdam(lr=1e-2, axis_name="dp",
                                       grad_sync_dtype="int8")
            state = opt.init(params, world_size=2)
            step = make_train_step(CFG, opt, mesh,
                                   telemetry=tel if with_tel else None)
            return {"p": params, "s": state, "step": step,
                    "stats": tel.init() if with_tel else None}

        a, b = make(True), make(False)
        for i in range(3):
            a["p"], a["s"], a["stats"], loss_a = a["step"](
                a["p"], a["s"], a["stats"], tokens, targets)
            b["p"], b["s"], loss_b = b["step"](
                b["p"], b["s"], tokens, targets)
            assert float(loss_a) == float(loss_b)
        _assert_bitwise(a["p"], b["p"])
        _assert_bitwise(a["s"], b["s"])
        assert int(a["stats"].steps) == 3

    def test_window_reset_does_not_retrace(self, devices8):
        """The fetch seam's init_like swap keeps the jit signature —
        compiled-variant count must not grow per fetch."""
        mesh = _mesh(devices8, 2)
        tokens, targets = _data(2)
        tel = stepstats.StepTelemetry()
        params = init_params(CFG, jax.random.PRNGKey(0))
        opt = FusedAdam(lr=1e-2)
        state = opt.init(params)
        step = make_train_step(CFG, opt, mesh, telemetry=tel)
        stats = tel.init()
        fetcher = stepstats.AsyncFetcher()
        for i in range(4):
            params, state, stats, _loss = step(params, state, stats,
                                               tokens, targets)
            if i % 2 == 1:  # fetch + reset mid-stream
                fetcher.put("stats", i, stats._asdict())
                stats = tel.init_like(stats)
        baseline = step._cache_size()
        for i in range(2):
            params, state, stats, _loss = step(params, state, stats,
                                               tokens, targets)
            fetcher.put("stats", i, stats._asdict())
            stats = tel.init_like(stats)
        assert step._cache_size() == baseline
        harvested = fetcher.flush()
        assert sum(int(t["steps"]) for _, _, t in harvested) >= 4


# ----------------------------------------------------------------- goodput
class TestGoodput:
    def test_flops_formulas(self):
        assert goodput.model_flops_per_token(10, 2, 4, 8) \
            == 6 * 10 + 12 * 2 * 4 * 8

    def _clock(self, start=1000.0):
        t = {"now": start}

        def fn():
            return t["now"]

        fn.advance = lambda dt: t.__setitem__("now", t["now"] + dt)
        return fn

    def test_fractions_sum_to_one_with_wedge_and_restart(self, tmp_path):
        clk = self._clock()
        # session 1: 10s productive, 2s checkpoint, then wedges for 4s
        a1 = goodput.GoodputAccountant(tmp_path, run_id="r", time_fn=clk)
        clk.advance(10)
        a1.step_done(steps=10, tokens=1000)
        with a1.attribute("checkpoint"):
            clk.advance(2)
        clk.advance(4)              # the wedged tail (no progress)
        a1.finalize("wedge")        # what the watchdog's on_wedge does
        clk.advance(6)              # supervisor backoff + relaunch gap
        # session 2: 8s productive, 1s restore, clean exit
        a2 = goodput.GoodputAccountant(tmp_path, run_id="r", time_fn=clk)
        with a2.attribute("restore"):
            clk.advance(1)
        clk.advance(8)
        a2.step_done(steps=8, tokens=800)
        a2.finalize("clean")
        rep = goodput.goodput_report(tmp_path)
        assert rep["sessions"] == 2
        assert rep["wall_secs"] == pytest.approx(31.0)
        f = rep["fractions"]
        assert sum(f.values()) == pytest.approx(1.0, abs=1e-9)
        assert rep["seconds"]["wedge"] == pytest.approx(4.0)
        assert rep["seconds"]["restart"] == pytest.approx(6.0)
        assert rep["seconds"]["checkpoint"] == pytest.approx(2.0)
        assert rep["seconds"]["restore"] == pytest.approx(1.0)
        assert rep["seconds"]["productive"] == pytest.approx(18.0)
        assert rep["wedge_events"] == 1
        assert rep["steps"] == 18 and rep["tokens"] == 1800

    def test_hard_killed_session_tail_lands_in_restart(self, tmp_path):
        clk = self._clock()
        a1 = goodput.GoodputAccountant(tmp_path, run_id="r", time_fn=clk)
        clk.advance(5)
        a1.step_done(steps=5)
        a1.heartbeat()          # last persist before the kill
        clk.advance(3)          # unpersisted progress, then SIGKILL
        # (no finalize — the process is gone)
        clk.advance(2)
        a2 = goodput.GoodputAccountant(tmp_path, run_id="r", time_fn=clk)
        clk.advance(4)
        a2.step_done(steps=4)
        a2.finalize("clean")
        rep = goodput.goodput_report(tmp_path)
        # killed session's end IS its last heartbeat; the 3+2s to the
        # relaunch are restart, and the fractions still close to 1
        assert rep["seconds"]["restart"] == pytest.approx(5.0)
        assert sum(rep["fractions"].values()) == pytest.approx(1.0)
        assert rep["exit_causes"] == [None, "clean"]

    def test_mfu_fields(self, tmp_path):
        clk = self._clock()
        a = goodput.GoodputAccountant(tmp_path, time_fn=clk)
        clk.advance(10)
        a.step_done(steps=10, tokens=10_000)
        a.finalize("clean")
        rep = goodput.goodput_report(tmp_path, flops_per_token=1e9,
                                     roofline_tflops=10.0)
        assert rep["tokens_per_sec_productive"] == pytest.approx(1000.0)
        assert rep["model_tflops_productive"] == pytest.approx(1.0)
        assert rep["mfu_vs_measured_roofline"] == pytest.approx(0.1)

    def test_report_tolerates_empty_and_torn(self, tmp_path):
        assert goodput.goodput_report(tmp_path)["sessions"] == 0
        (tmp_path / "goodput_session_torn.json").write_text("{not json")
        assert goodput.goodput_report(tmp_path)["sessions"] == 0

    def test_report_file_in_dir_is_not_a_session(self, tmp_path):
        """The aggregate goodput_report.json lives in the SAME dir and
        carries the same schema tag: a later session's report must
        skip it (the third-resume crash this pins)."""
        clk = self._clock()
        a = goodput.GoodputAccountant(tmp_path, time_fn=clk)
        clk.advance(2)
        a.step_done(steps=2)
        a.finalize("clean")
        rep1 = goodput.goodput_report(tmp_path)
        (tmp_path / "goodput_report.json").write_text(json.dumps(rep1))
        rep2 = goodput.goodput_report(tmp_path)
        assert rep2["sessions"] == 1
        assert abs(sum(rep2["fractions"].values()) - 1.0) < 1e-9


# ---------------------------------------------------------------- serving
class TestServingMetrics:
    def test_scheduler_records_queue_ttft_and_latency(self):
        from apex_tpu.inference import (
            ContinuousBatchingScheduler, DecodeConfig, KVCacheConfig,
            Request,
        )

        cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                        num_attention_heads=4, max_seq_len=64,
                        position_embedding_type="rope",
                        compute_dtype=jnp.float32, checkpoint_layers=False)
        params = init_params(cfg, jax.random.PRNGKey(0))
        dcfg = DecodeConfig(
            cache=KVCacheConfig(num_pages=10, page_size=4,
                                pages_per_seq=4, dtype=jnp.float32),
            max_batch=2, max_prompt_len=8, temperature=0.0,
            attn_impl="xla", sample_impl="xla",
            sample_dot_dtype=jnp.float32)
        with metrics.MetricsScope() as reg:
            sched = ContinuousBatchingScheduler(params, cfg, dcfg)
            for rid in range(3):
                sched.submit(Request(rid=rid, prompt=[1, 2, 3],
                                     max_new_tokens=3))
            done = sched.run_until_drained()
            assert len(done) == 3
            hist = {n: v for m in reg.metrics() for n, l, v in m.samples()}
            assert hist["apex_serve_ttft_seconds_count"] == 3
            assert hist["apex_serve_admission_wait_seconds_count"] == 3
            # inter-token: every decoded token after the first per seq
            decoded = sum(len(c.tokens) - 1 for c in done)
            assert hist["apex_serve_inter_token_seconds_count"] == decoded
            assert reg.counter("apex_serve_completions_total").value() == 3
            assert reg.counter(
                "apex_serve_generated_tokens_total").value() == sum(
                    len(c.tokens) for c in done)
            # drained: gauges read empty
            assert reg.gauge("apex_serve_queue_depth").value() == 0
            assert reg.gauge("apex_serve_active_slots").value() == 0

    def _tiny(self, max_batch=2):
        from apex_tpu.inference import (
            ContinuousBatchingScheduler, DecodeConfig, KVCacheConfig,
        )

        cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                        num_attention_heads=4, max_seq_len=64,
                        position_embedding_type="rope",
                        compute_dtype=jnp.float32, checkpoint_layers=False)
        dcfg = DecodeConfig(
            cache=KVCacheConfig(num_pages=20, page_size=4,
                                pages_per_seq=4, dtype=jnp.float32),
            max_batch=max_batch, max_prompt_len=8, temperature=0.0,
            attn_impl="xla", sample_impl="xla",
            sample_dot_dtype=jnp.float32)
        return ContinuousBatchingScheduler(
            init_params(cfg, jax.random.PRNGKey(0)), cfg, dcfg)

    def test_a_scope_entered_after_the_scheduler_was_built_gets_the_gauges(
            self):
        """The occupancy gauges' children are resolved once a REGISTRY:
        a scope entered later, and the registry behind it once it is
        left, each receive what is recorded while they are current."""
        from apex_tpu.inference import Request

        with metrics.MetricsScope() as first:
            sched = self._tiny()
            sched.submit(Request(rid=0, prompt=[1, 2, 3],
                                 max_new_tokens=4))
            assert first.gauge("apex_serve_queue_depth").value() == 1
            with metrics.MetricsScope() as second:
                sched.step()                # admit + prefill
                sched.step()                # launch: occupancy recorded
                sched.step()
                assert second.gauge("apex_serve_active_slots").value() == 1
                assert second.gauge("apex_serve_queue_depth").value() == 0
                assert second.gauge("apex_serve_lane_queue_depth",
                                    labelnames=("lane",)).value(
                                        lane="interactive") == 0
                free = second.gauge("apex_serve_free_pages").value()
                assert 0 < free < 19
            # the first registry saw none of it ...
            assert first.gauge("apex_serve_active_slots").value() == 0
            sched.run_until_drained()
            # ... and is written again once it is current
            assert first.gauge("apex_serve_free_pages").value() == 19
            assert first.gauge("apex_serve_active_slots").value() == 0
            assert second.gauge("apex_serve_active_slots").value() == 1

    def test_a_clash_in_the_callers_registry_does_not_stop_the_server(self):
        from apex_tpu.inference import Request

        with metrics.MetricsScope() as reg:
            reg.counter("apex_serve_queue_depth")   # the name, another kind
            sched = self._tiny()
            sched.submit(Request(rid=0, prompt=[1, 2, 3],
                                 max_new_tokens=3))
            (done,) = sched.run_until_drained()
            assert len(done.tokens) == 3
            assert reg.counter("apex_serve_completions_total").value() == 1

    def test_the_inter_token_exemplar_of_a_step_is_its_largest_gaps(self):
        """One ``observe_many`` a lane a step: a step's exemplar is the
        request whose gap was the largest, with that gap as its value."""
        from apex_tpu.inference import Request

        with metrics.MetricsScope() as reg:
            sched = self._tiny(max_batch=3)
            for rid in range(3):
                sched.submit(Request(rid=rid, prompt=[1 + rid, 2, 3],
                                     max_new_tokens=6))
            for _ in range(3):
                sched.step()
            hist = reg.histogram("apex_serve_inter_token_seconds",
                                 labelnames=("lane",))
            hist.drain_exemplars()
            steps = sched.stats["decode_steps"]
            # request 1's last token, as if it had come 5 s earlier
            slot = next(s for s in sched._slots if s.request.rid == 1)
            slot.token_times[-1] -= 5.0
            sched.step()
            assert sched.stats["decode_steps"] == steps + 1
            ((labels, ex),) = hist.drain_exemplars()
            assert labels == {"lane": "interactive"}
            assert ex["rid"] == 1 and ex["trace_id"] == slot.request.trace_id
            assert 5.0 < ex["value"] < 6.0
            count = {n: v for n, _, v in hist.samples()}[
                "apex_serve_inter_token_seconds_count"]
            assert count == 3 * sched.stats["decode_steps"]
