"""Request turnover without a device sync: plans drawn on the host's CPU
device, and each finished row's canvas read one call after its last
call."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core.samplers import loop
from repro.core.samplers.dndm import quantile_grid
from repro.core.samplers.registry import resolved_budget
from repro.models import Model, ModelConfig
from repro.serving import ContinuousScheduler, EngineConfig, GenerationEngine

VOCAB, SEQ, STEPS = 12, 8, 6


@pytest.fixture()
def telemetry():
    """Enable obs for one test; always restore the disabled default."""
    obs.metrics.reset()
    obs.tracing.clear()
    obs.enable()
    yield
    obs.metrics.reset()
    obs.tracing.clear()
    obs.disable()


class _ElemCfg:
    vocab_size = VOCAB


class _ElemModel:
    """Elementwise denoiser: row b's logits depend on row b alone, so a
    served row equals its solo replay bit for bit."""

    cfg = _ElemCfg()

    def denoise_fn(self, params, _cond=None):
        def fn(x_t, t, cond):
            k = jnp.arange(VOCAB, dtype=jnp.float32)
            n = jnp.arange(x_t.shape[-1], dtype=jnp.float32)
            t_ = jnp.asarray(t, jnp.float32).reshape(-1, 1, 1)
            return jnp.sin(x_t[..., None].astype(jnp.float32) * 0.37
                           + k * 1.11 + n[None, :, None] * 0.23
                           + t_ * 2.9) * 4.0
        return fn


def _engine(method="dndm", **kw):
    cfg = dict(method=method, steps=STEPS, shared_tau=False, nfe_budget=3,
               ddim_stride=2)
    cfg.update(kw)
    return GenerationEngine(_ElemModel(), {}, EngineConfig(**cfg))


# ------------------------------------------------------------------
# the plan, on the CPU device
# ------------------------------------------------------------------

def _expect_tau(rt, key, continuous=False):
    tau, x, k_loop = loop.setup(
        key, rt.noise, 1, SEQ, dist=rt.cdist if continuous else rt.dist,
        order=rt.order, shared=rt.shared_tau, continuous=continuous)
    return np.asarray(tau)[0], np.asarray(x)[0], k_loop


def _expect_dndm(rt, key):
    tau, x, k_loop = _expect_tau(rt, key)
    return loop.unique_times(tau), tau, x, k_loop


def _expect_static(rt, key):
    grid = quantile_grid(rt.dist, resolved_budget(rt, SEQ))
    tau, x, k_loop = _expect_tau(rt, key)
    idx = np.clip(np.searchsorted(grid, tau), 0, len(grid) - 1)
    return grid[::-1], grid[idx], x, k_loop


def _expect_grid(stride):
    def expect(rt, key):
        _, x, k_loop = loop.setup(key, rt.noise, 1, SEQ)
        return (np.arange(rt.steps, 0, -stride), None, np.asarray(x)[0],
                k_loop)
    return expect


def _expect_continuous(rt, key):
    tau, x, k_loop = _expect_tau(rt, key, continuous=True)
    return np.sort(tau)[::-1], tau, x, k_loop


@pytest.mark.parametrize("method,expect,noise_kind", [
    ("dndm", _expect_dndm, "absorbing"),
    ("dndm_topk", _expect_dndm, "absorbing"),
    ("dndm_static", _expect_static, "absorbing"),
    ("d3pm", _expect_grid(1), "absorbing"),
    ("ddim", _expect_grid(2), "multinomial"),
    ("dndm_c", _expect_continuous, "absorbing"),
])
def test_cpu_plan_matches_solo_setup(method, expect, noise_kind):
    """Every schedule_fn, run by ``plan_request`` on the CPU device with a
    fixed-length key split, gives the times, tau, x_T and per-call keys
    of ``loop.setup`` plus ``split(k_loop, nfe)`` under the same key."""
    eng = _engine(method, noise_kind=noise_kind)
    key = jax.random.PRNGKey(2**31 + 9)
    plan = eng.plan_request(key, SEQ, method)
    times, tau, x0, k_loop = expect(eng.runtime(), key)
    np.testing.assert_array_equal(plan.times, times)
    if tau is None:
        assert plan.tau is None
    else:
        np.testing.assert_array_equal(plan.tau, tau)
    np.testing.assert_array_equal(plan.x0, x0)
    np.testing.assert_array_equal(
        plan.step_keys, np.asarray(jax.random.split(k_loop, len(times))))
    assert plan.step_keys.shape == (plan.nfe, 2)
    for a in (plan.times, plan.x0, plan.step_keys):
        assert isinstance(a, np.ndarray)


def test_fixed_length_split_prefix():
    """The plans' key streams rest on this: under the repo's JAX config a
    split's first n keys do not depend on its length."""
    assert jax.config.jax_threefry_partitionable
    key = jax.random.PRNGKey(7)
    for T in (STEPS, 50):
        full = np.asarray(jax.random.split(key, T))
        for n in range(1, T + 1):
            np.testing.assert_array_equal(
                full[:n], np.asarray(jax.random.split(key, n)))


def test_plan_span_names_its_device(telemetry):
    sched = ContinuousScheduler(_engine(), max_batch=2, bucket_len=SEQ,
                                seed=3)
    sched.submit(SEQ)
    (rec,) = [r for r in obs.tracing.records() if r["name"] == "engine.plan"]
    assert rec["attrs"]["device"] == "cpu"
    assert sched.queue[0].key.devices() == {jax.devices("cpu")[0]}


# ------------------------------------------------------------------
# the harvest, one call later
# ------------------------------------------------------------------

def test_completion_surfaces_one_pump_after_its_last_call():
    """A request's last call frees its row; the next pump re-admits the
    row and completes the request, exactly once, with its own tokens and
    none of the request now in its row."""
    eng = _engine()
    sched = ContinuousScheduler(eng, max_batch=1, bucket_len=SEQ, seed=5)
    r1, r2 = sched.submit(SEQ), sched.submit(SEQ)
    req1, req2 = sched.queue
    runner = None
    for _ in range(req1.plan.nfe):
        assert sched.pump()
        runner = runner or next(iter(sched._runners.values()))
        assert r1 not in sched.done
    assert runner.free_rows() == [0] and runner.unread_rows() == [0]
    assert sched.pump()                 # r2 takes row 0, r1 is read
    assert list(sched.done) == [r1] and req2.t_admit > 0
    done = sched.run()
    assert sorted(done) == [r1, r2]
    for r in (req1, req2):
        solo, _ = eng.generate(r.key, 1, SEQ)
        np.testing.assert_array_equal(np.asarray(solo.tokens)[0], r.result)
    assert sched.total_calls == req1.plan.nfe + req2.plan.nfe
    assert not sched._retired and not runner.unread_rows()


def test_runner_returns_each_canvas_once():
    """Driven directly, the runner hands every plan's canvas back once,
    keyed by plan, the last ones by the read at the drain."""
    eng = _engine()
    plans = [eng.plan_request(jax.random.PRNGKey(i), SEQ) for i in range(3)]
    runner = eng.stepwise(2, SEQ)
    runner.admit_many([(0, plans[0]), (1, plans[1])])
    queue = [plans[2]]
    seen: list = []
    calls = 0
    while runner.active_rows() or runner.unread_rows():
        if queue and runner.free_rows():
            runner.admit(runner.free_rows()[0], queue.pop())
        before = runner.calls
        seen.extend(runner.step())
        calls += runner.calls - before
    assert sorted(map(id, seen)) == sorted(map(id, plans))
    assert runner.step() == {}
    assert calls == runner.calls


def test_run_drains_every_request_and_counts_calls(telemetry):
    """``run()`` completes every request across methods and lengths; the
    drain's read dispatches nothing, so ``engine.stepwise_calls`` equals
    ``total_calls``."""
    eng = _engine()
    sched = ContinuousScheduler(eng, max_batch=3, bucket_len=SEQ, seed=11)
    rids = [sched.submit(n, method=m) for n, m in
            [(SEQ, "dndm"), (5, "dndm"), (SEQ, "rdm"), (6, "dndm"),
             (7, "dndm_topk"), (SEQ, "dndm"), (4, "rdm")]]
    done = sched.run()
    assert sorted(done) == rids
    assert all(done[r].result.shape == (done[r].length,) for r in rids)
    assert not sched.queue and not sched._row_req and not sched._retired
    calls = sum(obs.counter("engine.stepwise_calls").value(method=m)
                for m in ("dndm", "rdm", "dndm_topk"))
    assert calls == sched.total_calls == eng.stepwise_dispatched
    assert all(r.padded_positions == 0 for r in sched._runners.values())


def test_other_group_completion_read_after_this_call():
    """A request of one group completes on the next pump even when that
    pump serves another group."""
    eng = _engine()
    sched = ContinuousScheduler(eng, max_batch=2, bucket_len=SEQ, seed=2)
    ra = sched.submit(SEQ, method="dndm")
    for _ in range(3):                  # rdm keeps work to the end
        sched.submit(SEQ, method="rdm")
    req_a = sched.queue[0]
    while req_a.plan not in sched._retired:
        sched.pump()
    assert ra not in sched.done
    rdm = sched._runners[("rdm", 0)]
    assert sched._rotation[sched._rr] == ("rdm", 0)
    calls = rdm.calls
    sched.pump()                        # an rdm call, then dndm's read
    assert rdm.calls == calls + 1 and ra in sched.done
    assert len(sched.run()) == 4


def test_lag_calls_reads_one_in_a_steady_backlog(telemetry):
    """With the queue kept full every read comes one call after the
    finished rows' last call; only the drain's read comes at once."""
    eng = _engine()
    sched = ContinuousScheduler(eng, max_batch=2, bucket_len=SEQ, seed=4)
    for _ in range(12):
        while len(sched.queue) < 2:
            sched.submit(SEQ)
        sched.pump()
    sched.run()
    lags = [r["attrs"]["lag_calls"] for r in obs.tracing.records()
            if r["name"] == "engine.harvest"]
    rows = [r["attrs"]["rows"] for r in obs.tracing.records()
            if r["name"] == "engine.harvest"]
    assert len(lags) >= 3
    assert lags[:-1] == [1] * (len(lags) - 1) and lags[-1] == 0
    assert obs.counter("engine.harvests_deferred").value(
        method="dndm") == sum(rows[:-1])
    assert sum(rows) == len(sched.done)


# ------------------------------------------------------------------
# nothing compiles at turnover once the benchmark's warm-up has run
# ------------------------------------------------------------------

def test_backlog_turnovers_after_warm_make_no_lowerings():
    """After ``perfbench.run.warm`` and the scheduler's construction, a
    backlog of turnovers (key, plan, admit, step, deferred harvest)
    lowers no program, the first submit included: the window's
    ``window_compiles`` check counts lowerings with this listener."""
    from perfbench import run
    cfg = ModelConfig(name="turnover", arch_type="dense", n_layers=1,
                      d_model=32, n_heads=2, n_kv_heads=1, d_ff=64,
                      vocab_size=VOCAB, block_pattern=("attn",),
                      bidirectional=True)
    model = Model(cfg)
    eng = GenerationEngine(model, model.init(jax.random.PRNGKey(0)),
                           EngineConfig(method="dndm", steps=STEPS,
                                        shared_tau=True))
    traffic = {"canvas": SEQ, "max_batch": 3, "method": "dndm",
               "steps": STEPS}
    run.warm(eng, traffic)
    sched = ContinuousScheduler(eng, max_batch=3, bucket_len=SEQ,
                                seed=2**31 + 3)
    mark = len(run._COMPILES)
    while len(sched.done) < 12:
        while len(sched.queue) < 6:
            sched.submit(SEQ)
        sched.pump()
    assert len(run._COMPILES) == mark
