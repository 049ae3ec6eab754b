"""Generation engine: one object that binds (model params, sampler family)
and serves batched requests.

The engine has no per-method branches: every sampler is dispatched
through ``repro.core.samplers.registry``, so the benchmarks and the
serving launcher compare apples-to-apples and a newly registered sampler
is immediately servable (``registry.names()`` is the method list).

For conditional requests, ``cond={"prefix_tokens": src}``: the model
wrapper feeds [src | x_t] with bidirectional attention and returns target
logits, so samplers stay prefix-agnostic.

The weights reach every compiled sampler and stepwise call as an
argument, inside the ``cond`` pytree the samplers pass through untouched
(:meth:`GenerationEngine.call_cond`).  A denoiser that closed over them
would bake them into each program as constants: at published widths that
is hundreds of MB per executable, compiled and held on the device once
per program.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import decode as decode_lib
from repro.core import schedules as sched_lib
from repro.core import transition as trans_lib
from repro.core.noise import NoiseDist
from repro.core.samplers import SamplerConfig, SamplerOutput, registry
from repro.core.samplers.stepwise import CallSchedule
from repro.models.model import Model


@dataclasses.dataclass
class EngineConfig:
    method: str = "dndm"
    steps: int = 50                   # T for discrete methods / MP iters
    schedule: str = "linear"
    noise_kind: str = "absorbing"
    beta: tuple[float, float] | None = None   # Beta approx of D_tau
    nfe_budget: int = 0               # static variants
    x0_mode: str = "sample"
    temperature: float = 1.0
    order: str = "iid"                # iid | l2r | r2l
    shared_tau: bool = True           # one tau-set per batch (paper NFE)
    ddim_stride: int = 1              # DDIM baseline subsequence stride


def host_device() -> jax.Device:
    """The host's CPU device, where requests' keys and plans are drawn.

    A plan is a handful of small programs and a copy to the host; drawn
    on the accelerator, its first read waits for every call queued there
    and the programs then run one at a time with the accelerator idle.
    Raises when JAX has no CPU backend: there is no fallback."""
    return jax.devices("cpu")[0]


class GenerationEngine:
    def __init__(self, model: Model, params, engine_cfg: EngineConfig):
        self.model = model
        self.params = params
        self.cfg = engine_cfg
        v = model.cfg.vocab_size
        if engine_cfg.noise_kind == "absorbing":
            from repro.core.noise import absorbing
            self.noise: NoiseDist = absorbing(v)
        else:
            from repro.core.noise import multinomial
            self.noise = multinomial(v)
        self.check_method(engine_cfg.method)    # fail fast, list alternatives
        model_fn = model.denoise_fn

        def denoise(x_t, t, c):
            return model_fn(c["params"])(x_t, t, c["cond"])
        self.denoise_fn = denoise
        self._law_cache: dict = {}
        self._jit_cache: dict = {}
        self._host_warm: set = set()    # host-sampler per-step jit warm keys
        # batched stepwise calls dispatched by every runner of this
        # engine: the clock of a deferred harvest's ``lag_calls``
        self.stepwise_dispatched = 0

    def check_method(self, name: str) -> registry.SamplerSpec:
        """Resolve a method and validate it against the engine's noise
        kind (also used by the scheduler before enqueueing overrides)."""
        spec = registry.get(name)
        noise = getattr(self, "noise", None)
        if noise is not None and noise.kind not in spec.noise_kinds:
            raise ValueError(
                f"{spec.name} supports {sorted(spec.noise_kinds)} noise, "
                f"engine is configured with {noise.kind!r}")
        return spec

    def _laws(self):
        """(schedule, dist, cdist) derived from the *current* config —
        mutating steps/schedule/beta must never serve stale laws."""
        c = self.cfg
        lk = (c.schedule, c.steps, c.beta)
        if lk not in self._law_cache:
            schedule = sched_lib.get(c.schedule, c.steps)
            if c.beta:
                a, b = c.beta
                dist = trans_lib.beta_approx(c.steps, a, b)
                cdist = trans_lib.beta_continuous(a, b)
            else:
                dist = trans_lib.from_schedule(schedule)
                cdist = trans_lib.beta_continuous(17, 4)
            self._law_cache[lk] = (schedule, dist, cdist)
        return self._law_cache[lk]

    def call_cond(self, cond: dict | None) -> dict:
        """The ``cond`` argument of a sampler call: the request's own
        conditioning plus the weights, which ``denoise_fn`` unpacks."""
        return {"params": self.params, "cond": cond}

    def runtime(self) -> registry.SamplerRuntime:
        c = self.cfg
        schedule, dist, cdist = self._laws()
        return registry.SamplerRuntime(
            denoise_fn=self.denoise_fn, noise=self.noise,
            schedule=schedule, dist=dist, cdist=cdist,
            cfg=SamplerConfig(x0_mode=c.x0_mode, temperature=c.temperature),
            steps=c.steps, nfe_budget=c.nfe_budget, order=c.order,
            shared_tau=c.shared_tau, ddim_stride=c.ddim_stride)

    def _cache_key(self, method: str, batch: int, N: int,
                   rt: registry.SamplerRuntime, cond: dict | None):
        # every knob that changes the traced computation must be in the
        # key — reconfiguring the engine (steps, beta, nfe_budget, order,
        # ...) must never serve a stale compiled sampler.  cond structure
        # is part of the key too: the cached callable is AOT-compiled, so
        # it is specialized to the conditioning shapes/dtypes.
        c = self.cfg
        cond_key = None if cond is None else tuple(
            sorted((k, v.shape, str(v.dtype)) for k, v in cond.items()))
        return (method, batch, N, c.schedule, c.beta, rt.steps,
                rt.nfe_budget, rt.order, rt.shared_tau, rt.ddim_stride,
                rt.cfg, cond_key)

    def generate(self, key, batch: int, N: int, cond: dict | None = None,
                 method: str | None = None):
        """Returns (SamplerOutput, wall_seconds).

        ``method`` overrides the engine's configured sampler per call —
        one engine instance can serve every registered method.

        ``wall_seconds`` measures steady-state execution only, for both
        sampler kinds.  Scan samplers compile a jit-cache miss ahead of
        the timed run (``.lower().compile()``); host samplers run the
        sampler once untimed on the first call per (shape, knob) key so
        the per-step jit caches are warm, then time a second run under
        the same PRNG key (identical output).  Either way the one-time
        cost is reported as ``aux["compile_seconds"]`` (0.0 on a warm
        key), so benchmarks never attribute trace time to the sampler.

        With ``repro.obs`` enabled, every call is an ``engine.generate``
        trace span (method/kind/batch/seq + nfe/wall/cache/backend) and
        feeds the engine.* metrics.
        """
        m = method or self.cfg.method
        spec = self.check_method(m)
        rt = self.runtime()
        with obs.span("engine.generate", method=m, kind=spec.kind,
                      batch=batch, seq=N) as sp:
            out, wall, cache = self._run(key, spec, m, rt, batch, N, cond)
            if obs.enabled():
                backend = decode_lib.resolve_backend()
                compile_s = out.aux.get("compile_seconds", 0.0)
                obs.counter("engine.requests").inc(method=m, kind=spec.kind)
                obs.counter("engine.nfe").inc(out.nfe, method=m)
                obs.counter("engine.tokens").inc(batch * N, method=m)
                obs.histogram("engine.wall_seconds").observe(wall, method=m)
                if compile_s:
                    obs.histogram("engine.compile_seconds").observe(
                        compile_s, method=m, kind=spec.kind)
                sp.set(nfe=out.nfe, wall_s=wall, compile_s=compile_s,
                       cache=cache, backend=backend)
        return out, wall

    def plan_request(self, key, N: int,
                     method: str | None = None) -> CallSchedule:
        """The request's predetermined call schedule, known at admission.

        DNDM's structural claim as an API: sampling the transition-time
        set under ``key`` determines every network call the request will
        ever make (times, per-call key stream, x_T) before sampling
        starts.  The continuous scheduler calls this at ``submit()``.

        The draw runs on the host's CPU device (:func:`host_device`),
        whatever device ``key`` is on, and the plan comes back as host
        arrays, so planning never waits for the calls queued on the
        accelerator.  Every request of a method runs the same fixed set
        of CPU programs.  The ``engine.plan`` span (``device``) covers
        the draw and its copies to the host.
        """
        m = method or self.cfg.method
        spec = self.check_method(m)
        if spec.schedule_fn is None:
            raise ValueError(f"{m} does not expose a call schedule")
        rt, cpu = self.runtime(), host_device()
        with obs.span("engine.plan", method=m, device=cpu.platform), \
                jax.default_device(cpu):
            return spec.schedule_fn(jax.device_put(key, cpu), rt, N)

    def stepwise(self, rows: int, N: int, method: str | None = None,
                 prefix_len: int = 0) -> "StepwiseRunner":
        """A row-resumable runner: ``rows`` independent request slots of
        length ``N``, advanced one own-schedule step per batched call.
        ``prefix_len > 0`` makes it a conditional runner — every admitted
        request must carry a prefix of exactly that length."""
        return StepwiseRunner(self, method or self.cfg.method, rows, N,
                              prefix_len=prefix_len)

    def _run(self, key, spec, m: str, rt, batch: int, N: int, cond):
        """Dispatch one request; returns (out, steady wall, hit|miss)."""
        ck = self._cache_key(m, batch, N, rt, cond)
        if spec.kind == "host":
            # host-driven: data-dependent NFE, per-step jit inside the
            # sampler module hits its own cache.  A cold key folds the
            # per-step trace time into the first walk, so warm it with
            # one untimed run — the timed run repeats the same key and
            # returns the identical output.
            missed = ck not in self._host_warm
            warm_wall = 0.0
            if missed:
                tc = time.time()
                # the warm-up re-executes the exact run measured below;
                # recording it would double-count sampler.step events,
                # step/reveal histograms and decode.* counters on every
                # jit-cache miss, so obs is suppressed for its duration
                with obs.suppressed():
                    warm = spec.run(key, rt, batch, N, self.call_cond(cond))
                    jax.block_until_ready(warm.tokens)
                warm_wall = time.time() - tc
                self._host_warm.add(ck)
            t0 = time.time()
            out = spec.run(key, rt, batch, N, self.call_cond(cond))
            jax.block_until_ready(out.tokens)
            wall = time.time() - t0
            # estimated per-step jit warm-up: cold walk minus steady walk
            out.aux["compile_seconds"] = (max(0.0, warm_wall - wall)
                                          if missed else 0.0)
        else:
            # scan-based samplers have a statically known NFE, so the
            # whole sampler is AOT-compiled once per (shape, knobs, cond
            # structure) and reused across requests.
            compile_s = 0.0
            missed = ck not in self._jit_cache
            if missed:
                run = spec.run
                tc = time.time()
                call = jax.jit(
                    lambda k, c: run(k, rt, batch, N, c).tokens,
                ).lower(key, self.call_cond(cond)).compile()
                compile_s = time.time() - tc
                self._jit_cache[ck] = (call, spec.static_nfe(rt, N))
            call, nfe = self._jit_cache[ck]
            t0 = time.time()        # timed run starts after compilation
            out = SamplerOutput(tokens=call(key, self.call_cond(cond)),
                                nfe=nfe,
                                aux={"compile_seconds": compile_s})
            jax.block_until_ready(out.tokens)
            wall = time.time() - t0
        name = ("engine.jit_cache.misses" if missed
                else "engine.jit_cache.hits")
        obs.counter(name).inc(method=m, kind=spec.kind)
        return out, wall, ("miss" if missed else "hit")


class StepwiseRunner:
    """Fixed-shape rolling batch of row-resumable requests.

    ``rows`` slots share one compiled batched step; each occupied slot
    carries a request's :class:`CallSchedule` and a pointer into it.
    Every :meth:`step` is ONE network call that advances *every* live row
    by one entry of its own schedule — rows sit at different diffusion
    times (the denoiser takes per-row ``t_norm``) and draw their noise
    from their own per-request key stream, so each request's trajectory
    is bit-for-bit the solo batch-of-one run under the same key stream.
    Free slots pass through untouched (parked at a sentinel time outside
    every schedule — T+1 on a discrete grid, 2.0 in continuous time —
    and additionally gated out inside every row step), and a slot is
    re-admittable the moment its request completes — mid-flight
    admission costs nothing but an ``.at[row].set``.

    ``prefix_len > 0`` makes the runner conditional: it keeps a
    ``(rows, prefix_len)`` prefix buffer fed to the denoiser as
    ``cond={"prefix_tokens": ...}`` and every admission must supply a
    prefix of exactly that length (the continuous scheduler groups
    conditional traffic by (method, prefix length), so rows are never
    padded and per-row solo parity is preserved).  Free rows hold the
    noise pad token.

    A request's last call is known from its plan, so its row is freed
    when that call is dispatched and is re-admittable at the next step
    boundary.  Its canvas, the output of that call, is read one call
    later: after the next call of any runner of the engine has been
    dispatched, so the read waits only for the request's own last call
    while the next one runs, and the accelerator's queue never drains at
    a request's turnover.  Results come back keyed by the request's
    plan, not its row, which may already hold the next request; each
    finished canvas is read once, so results are exactly-once.  Before a
    call is dispatched the host waits for the call two back, never the
    one before: one call in flight hides one copy, and the host runs at
    most two calls ahead of the device.

    ``padded_positions`` counts the positions of the live rows that lie
    past their request's length: work each call computes and the caller
    cuts off.  The runner does not know the lengths; the caller that
    does (``ContinuousScheduler``) keeps the sum at admission and
    completion, and every ``engine.stepwise`` span reports it.
    """

    def __init__(self, engine: GenerationEngine, method: str, rows: int,
                 N: int, prefix_len: int = 0):
        spec = engine.check_method(method)
        if spec.stepwise_step is None:
            raise ValueError(
                f"{method} has no stepwise step; stepwise-capable methods: "
                f"{', '.join(n for n in registry.names() if registry.get(n).stepwise_step)}")
        self.engine = engine
        self.method = method
        self.spec = spec
        self.rt = engine.runtime()
        self.rows = rows
        self.N = N
        self.prefix_len = prefix_len
        if spec.continuous_time:
            # timestamps live in (0, 1]; 2.0 is past every schedule
            self._t_dtype, self._t_free = np.float32, 2.0
        else:
            self._t_dtype, self._t_free = np.int32, self.rt.dist.T + 1
        self.x = jnp.zeros((rows, N), jnp.int32)
        self.revealed = jnp.zeros((rows, N), bool)
        self.tau = jnp.zeros((rows, N), jnp.dtype(self._t_dtype))
        self.prefix = (jnp.full((rows, prefix_len), engine.noise.pad_id,
                                jnp.int32) if prefix_len else None)
        self._plans: list[CallSchedule | None] = [None] * rows
        self._ptr = [0] * rows
        # rows whose last call ran but whose canvas is unread: that
        # call's output canvas, the engine's dispatch count after it, and
        # the (row, plan) pairs it finished
        self._unread: tuple[jax.Array, int,
                            list[tuple[int, CallSchedule]]] | None = None
        self._recent: deque = deque(maxlen=2)   # last two calls' outputs
        self.calls = 0                          # batched network calls
        self.padded_positions = 0               # kept by the caller

    def free_rows(self) -> list[int]:
        return [i for i in range(self.rows) if self._plans[i] is None]

    def active_rows(self) -> list[int]:
        return [i for i in range(self.rows) if self._plans[i] is not None]

    def unread_rows(self) -> list[int]:
        """Rows whose request's last call was dispatched but whose canvas
        has not been read; each may already hold the next request."""
        return [] if self._unread is None else [i for i, _ in
                                                self._unread[2]]

    def admit(self, row: int, plan: CallSchedule,
              prefix: np.ndarray | None = None) -> None:
        """Install a request's plan into a free slot (any step boundary)."""
        self.admit_many([(row, plan)],
                        None if prefix is None else [prefix])

    def admit_many(self, pairs: list[tuple[int, CallSchedule]],
                   prefixes: list[np.ndarray] | None = None) -> None:
        """Install several plans with ONE scatter per buffer — the per-op
        dispatch cost of ``.at[row].set`` dominates admission otherwise.

        Plans must carry (x0, step_keys); ``tau`` is additionally
        required for the tau-consuming methods (the DNDM family) and
        ignored by the schedule-driven baselines (``tau=None`` plans).
        Finished canvases come back keyed by plan, so each request needs
        a plan object of its own.
        ``prefixes`` (aligned with ``pairs``) is required iff the runner
        was built with ``prefix_len > 0``.  The ``engine.admit`` span
        (``rows`` admitted) covers the host stacking, the copies to the
        device and the dispatch of the scatters.
        """
        if not pairs:
            return
        if bool(prefixes) != bool(self.prefix_len):
            raise ValueError(
                "conditional runner needs one prefix per admission"
                if self.prefix_len else
                "unconditional runner cannot admit prefixes")
        for row, plan in pairs:
            if self._plans[row] is not None:
                raise ValueError(f"row {row} is occupied")
            if plan.x0 is None or plan.step_keys is None:
                raise ValueError("stepwise admission needs a full plan "
                                 "(x0, step_keys) — see samplers/stepwise")
        with obs.span("engine.admit", method=self.method, rows=len(pairs)):
            idx = jnp.asarray([row for row, _ in pairs], jnp.int32)
            x0 = np.stack([np.asarray(p.x0, np.int32).reshape(self.N)
                           for _, p in pairs])
            tau = np.stack([
                np.zeros(self.N, self._t_dtype) if p.tau is None
                else np.asarray(p.tau, self._t_dtype).reshape(self.N)
                for _, p in pairs])
            self.x = self.x.at[idx].set(jnp.asarray(x0))
            self.revealed = self.revealed.at[idx].set(False)
            self.tau = self.tau.at[idx].set(jnp.asarray(tau))
            if self.prefix_len:
                pre = np.stack([
                    np.asarray(p, np.int32).reshape(self.prefix_len)
                    for p in prefixes])
                self.prefix = self.prefix.at[idx].set(jnp.asarray(pre))
        for row, plan in pairs:
            self._plans[row] = plan
            self._ptr[row] = 0

    def step(self) -> dict[CallSchedule, np.ndarray]:
        """One batched network call; returns the whole canvas row of each
        request whose last call was an earlier one, keyed by its plan.

        With no live row and canvases unread (a drain), no call is
        dispatched and the canvases are read at once.

        The ``engine.stepwise`` span covers the host's preparation of the
        call's per-row times and keys and the dispatch of the call, not
        the device's execution, which runs after the span closes and is
        timed by the device trace.  Its attributes: ``rows`` live,
        ``padded_positions`` (see the class docstring) and, with
        telemetry on, ``request_ids``, the trace identity of each row the
        call advanced (comma-joined), the per-call backbone of
        ``obs.timeline(request_id)``.  The read is the ``engine.harvest``
        span (see :meth:`harvest`).
        """
        active = self.active_rows()
        if not active:
            return self.harvest()
        if len(self._recent) == self._recent.maxlen:
            self._recent[0].block_until_ready()     # call k-1, never k
        attrs = {"method": self.method, "call": self.calls,
                 "rows": len(active),
                 "padded_positions": self.padded_positions}
        if obs.enabled():
            attrs["request_ids"] = ",".join(
                p.request_id for i in active
                if (p := self._plans[i]).request_id is not None)
        with obs.span("engine.stepwise", **attrs):
            t_row = np.full((self.rows,), self._t_free, self._t_dtype)
            keys = np.zeros((self.rows, 2), np.uint32)
            for i in active:
                plan = self._plans[i]
                t_row[i] = plan.times[self._ptr[i]]
                keys[i] = plan.step_keys[self._ptr[i]]
            cond = (None if self.prefix is None
                    else {"prefix_tokens": self.prefix})
            state = self.spec.stepwise_step(
                {"x": self.x, "revealed": self.revealed},
                self.tau, jnp.asarray(t_row), jnp.asarray(keys),
                self.engine.call_cond(cond), self.rt)
            self.x, self.revealed = state["x"], state["revealed"]
        self._recent.append(self.x)
        self.calls += 1
        self.engine.stepwise_dispatched += 1
        if obs.enabled():
            obs.counter("engine.stepwise_calls").inc(method=self.method)
        ready, self._unread = self._unread, None
        finished = []
        for i in active:
            self._ptr[i] += 1
            if self._ptr[i] == len(self._plans[i].times):
                finished.append((i, self._plans[i]))
                self._plans[i] = None
        if finished:
            self.x.copy_to_host_async()
            self._unread = (self.x, self.engine.stepwise_dispatched,
                            finished)
        return self._read(ready)

    def harvest(self) -> dict[CallSchedule, np.ndarray]:
        """Read the unread canvases now: the whole canvas row of each
        request whose last call was dispatched, keyed by its plan.

        The scheduler calls it after another runner's call has been
        dispatched; :meth:`step` calls it at a drain.  The
        ``engine.harvest`` span covers the read, which waits for the
        request's last call, and the copies.  Its attributes: ``rows``
        read and ``lag_calls``, the calls of any runner of the engine
        dispatched after that last call (1 in a steady stream, 0 at a
        drain)."""
        ready, self._unread = self._unread, None
        return self._read(ready)

    def _read(self, ready) -> dict[CallSchedule, np.ndarray]:
        if ready is None:
            return {}
        canvas, seq, finished = ready
        lag = self.engine.stepwise_dispatched - seq
        with obs.span("engine.harvest", method=self.method,
                      rows=len(finished), lag_calls=lag):
            # one transfer of the whole buffer: cheaper than per-row
            # device slices
            host_x = np.asarray(canvas)
            done = {plan: host_x[i].copy() for i, plan in finished}
        if lag and obs.enabled():
            obs.counter("engine.harvests_deferred").inc(
                len(finished), method=self.method)
        return done
