"""Request schedulers: drain-mode batching and continuous NFE-aware batching.

Two schedulers share the :class:`Request` record and the engine:

* :class:`BatchScheduler` — drain mode: requests are grouped by method
  into fixed-shape power-of-two buckets and each batch runs a whole
  sampler trajectory before the next batch starts.  Simple, but a
  request arriving one step after a batch launches waits out the whole
  batch, and with independent per-request tau sets the batch walks the
  *union* of every row's transition times — rows pay NFE for steps where
  they do not transition.
* :class:`ContinuousScheduler` — continuous mode: ``submit()`` samples
  the request's predetermined call schedule (``engine.plan_request``, the
  DNDM structural property as an API), and a rolling
  :class:`~repro.serving.engine.StepwiseRunner` batch admits requests at
  any step boundary into free rows.  Every batched call advances each
  live row by one entry of *its own* schedule, so no row ever pays for a
  step where it has no transition — per-request NFE stays at the solo
  ``|unique tau|`` while the batch stays full.

Methods are validated against the sampler registry at submit time;
requests naming different methods are batched separately so each batch
hits one compiled sampler.
"""
from __future__ import annotations

import dataclasses
import itertools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.obs import slo as slo_lib
from repro.serving.engine import (GenerationEngine, StepwiseRunner,
                                  host_device)

# process-wide request-id mint: ids stay unique across scheduler
# instances so one trace file can hold several schedulers' requests
_next_request_id = itertools.count(1).__next__


def mint_request_id() -> str:
    return f"req-{_next_request_id():06d}"


@dataclasses.dataclass
class Request:
    rid: int
    length: int
    prefix: np.ndarray | None = None        # (P,) source tokens
    method: str | None = None               # resolved at submit time
    result: np.ndarray | None = None
    nfe: int = 0
    wall: float = 0.0                       # amortized share of batch_wall
    batch_wall: float = 0.0                 # wall-clock of the whole batch
    batch_size: int = 0                     # requests served in that batch
    # lifecycle timestamps (time.time()): queue latency = t_admit -
    # t_submit, service time = t_done - t_admit
    t_submit: float = 0.0
    t_admit: float = 0.0
    t_done: float = 0.0
    # continuous mode: the per-request key + predetermined call schedule
    # (set at submit) — replaying engine.generate(key, 1, N, method=...)
    # solo reproduces this request's tokens
    key: jax.Array | None = None
    plan: object | None = None
    steps_executed: int = 0
    steps_skipped: int = 0
    # trace identity, minted at submit(): every span/event this request
    # touches carries it, so obs.timeline(request_id) reconstructs the
    # full submit -> admission -> per-call -> completion history
    request_id: str = ""


class BatchScheduler:
    """Greedy fixed-bucket batching, grouped by sampler method."""

    def __init__(self, engine: GenerationEngine, max_batch: int = 8,
                 bucket_len: int = 64, seed: int = 0):
        self.engine = engine
        self.max_batch = max_batch
        self.bucket_len = bucket_len
        self.queue: list[Request] = []
        self.done: dict[int, Request] = {}
        self._rid = 0
        self._key = jax.random.PRNGKey(seed)

    def submit(self, length: int, prefix: np.ndarray | None = None,
               method: str | None = None) -> int:
        # normalize to a concrete method so explicit-default and default
        # requests land in the same batch, and fail fast (unknown name /
        # incompatible noise) — once a batch is popped in run() there is
        # no requeue path for it
        method = method or self.engine.cfg.method
        self.engine.check_method(method)
        self._rid += 1
        req = Request(self._rid, length, prefix, method)
        req.request_id = mint_request_id()
        req.t_submit = time.time()
        if obs.enabled():
            obs.event("scheduler.submit", request_id=req.request_id,
                      method=method, length=length, mode="drain")
        self.queue.append(req)
        return self._rid

    def batch_bucket(self, n: int) -> int:
        """Compiled batch size serving a group of ``n`` requests: the next
        power of two, capped at ``max_batch`` — a handful of (batch, N)
        shapes instead of one jit-cache entry per distinct queue size."""
        b = 1
        while b < n:
            b *= 2
        return min(b, self.max_batch)

    def _buckets(self) -> list[list[Request]]:
        """Split the queue into per-method FIFO batches of up to
        ``max_batch``, one grouping pass over the queue (methods keep
        first-arrival order).  Replaces the per-pop whole-queue rescan
        that made a mixed-method drain O(n^2)."""
        order: list[str] = []
        groups: dict[str, list[Request]] = {}
        for r in self.queue:
            if r.method not in groups:
                groups[r.method] = []
                order.append(r.method)
            groups[r.method].append(r)
        self.queue = []
        return [groups[m][i:i + self.max_batch] for m in order
                for i in range(0, len(groups[m]), self.max_batch)]

    def run(self) -> dict[int, Request]:
        """Drain the queue; returns completed requests by id.

        Each request records the *amortized* per-request wall share
        (``wall = batch_wall / batch_size``) plus the batch totals
        (``batch_wall``, ``batch_size``) — the batch runs once for all
        its members, so attributing the full wall-clock to every request
        would overcount serving cost by the batch size.
        """
        pending = len(self.queue)
        for batch in self._buckets():
            if obs.enabled():
                obs.gauge("scheduler.queue_depth").set(pending)
            pending -= len(batch)
            # pad the batch dim to the compiled bucket; padded rows are
            # generated (wasted work bounded by 2x) and sliced off below
            B = self.batch_bucket(len(batch))
            N = self.bucket_len
            m = batch[0].method
            cond = None
            if batch[0].prefix is not None:
                # left-pad short prefixes with the noise pad token ([MASK]
                # for absorbing) — padding with 0, a real vocab token,
                # would condition the row on spurious content.  A row's
                # reference run is therefore solo generation with the
                # same pad-extended prefix.
                P = max(len(r.prefix) for r in batch)
                pre = np.full((B, P), self.engine.noise.pad_id, np.int32)
                for i, r in enumerate(batch):
                    pre[i, P - len(r.prefix):] = r.prefix
                cond = {"prefix_tokens": jnp.asarray(pre)}
            self._key, k = jax.random.split(self._key)
            t_admit = time.time()
            rids = ",".join(r.request_id for r in batch)
            with obs.span("scheduler.batch", method=m, requests=len(batch),
                          bucket=B, request_ids=rids) as sp:
                if obs.enabled():
                    for r in batch:
                        obs.event("scheduler.admit",
                                  request_id=r.request_id, method=m,
                                  mode="drain",
                                  queue_s=t_admit - r.t_submit)
                out, wall = self.engine.generate(k, B, N, cond=cond,
                                                 method=m)
                if obs.enabled():
                    obs.counter("scheduler.batches").inc(method=m)
                    obs.counter("scheduler.requests").inc(len(batch),
                                                          method=m)
                    obs.counter("scheduler.padded_rows").inc(B - len(batch),
                                                             method=m)
                    obs.histogram("scheduler.occupancy").observe(
                        len(batch) / B, method=m)
                    obs.histogram("scheduler.batch_wall_seconds").observe(
                        wall, method=m)
                    sp.set(wall_s=wall, padded_rows=B - len(batch),
                           occupancy=len(batch) / B)
            toks = np.asarray(jax.device_get(out.tokens))
            share = wall / len(batch)
            t_done = time.time()
            for i, r in enumerate(batch):
                r.result = toks[i, : r.length]
                r.nfe = out.nfe
                r.wall = share
                r.batch_wall = wall
                r.batch_size = len(batch)
                r.t_admit = t_admit
                r.t_done = t_done
                if obs.enabled():
                    obs.histogram("scheduler.queue_latency_seconds").observe(
                        t_admit - r.t_submit, mode="drain")
                    obs.histogram("scheduler.service_seconds").observe(
                        t_done - t_admit, mode="drain")
                    obs.event("scheduler.complete",
                              request_id=r.request_id, method=r.method,
                              mode="drain", nfe=r.nfe,
                              service_s=t_done - t_admit)
                    slo_lib.observe_request(
                        r.method, latency_s=t_done - t_admit,
                        queue_s=t_admit - r.t_submit, nfe=r.nfe)
                self.done[r.rid] = r
        return self.done


class ContinuousScheduler:
    """Continuous NFE-aware batching over a rolling stepwise batch.

    ``submit()`` samples the request's predetermined call schedule
    immediately (``engine.plan_request`` under a per-request key), so the
    scheduler knows every network call the request will make before it is
    admitted.  A :class:`~repro.serving.engine.StepwiseRunner` holds up
    to ``max_batch`` in-flight rows; :meth:`pump` admits queued requests
    into free rows at the current step boundary (no drain barrier) and
    issues one batched network call advancing every live row along its
    own schedule.  Steps outside a request's schedule are never executed
    for it — per-request ``steps_skipped`` (= T - |unique tau|) counts
    the no-op grid steps the predetermined schedule proved unnecessary,
    and the batch-level call count is ``max`` over the cohort's schedule
    lengths instead of drain mode's ``|union|``.

    Per-request results are bit-for-bit the solo
    ``engine.generate(request.key, 1, N, method=...)`` run whenever the
    denoiser is batch-shape-invariant, and exactly reproducible from
    ``request.key`` regardless (same tau set, same per-step key stream;
    see ``samplers/stepwise.py`` for the parity contract).

    Each request's tokens come back on the pump after its last call: the
    runner frees the row when that call is dispatched and reads the
    canvas after the next call (see
    :class:`~repro.serving.engine.StepwiseRunner`), so nothing on the
    turnover path waits for the accelerator.

    Requests are grouped by (method, prefix length) — every registered
    method has a stepwise step, and conditional (prefix) requests get a
    conditional runner per exact prefix length, so prefixes are never
    padded inside a rolling batch and the solo-parity contract holds for
    them too.  Groups with work are served **round-robin** (one pump
    each, in first-arrival order of the group): a steady stream of one
    method can never starve queued requests of another — a group with
    work waits at most ``#groups-with-work - 1`` pumps for its next
    batched call.
    """

    def __init__(self, engine: GenerationEngine, max_batch: int = 8,
                 bucket_len: int = 64, seed: int = 0):
        self.engine = engine
        self.max_batch = max_batch
        self.bucket_len = bucket_len
        self.queue: list[Request] = []
        self.done: dict[int, Request] = {}
        self._rid = 0
        with jax.default_device(host_device()):
            self._key = jax.random.PRNGKey(seed)
            # compile the per-request key derivation now: on the first
            # submit its CPU compile (0.2-0.4 s) would stall serving
            jax.random.fold_in(self._key, 0)
        # group = (method, prefix_len); 0 = unconditional
        self._runners: dict[tuple, StepwiseRunner] = {}
        self._rotation: list[tuple] = []    # groups in first-seen order
        self._rr = 0                        # round-robin cursor
        self._row_req: dict[tuple, Request] = {}  # (group, row) -> request
        # requests whose last call was dispatched, canvas not yet read
        self._retired: dict = {}                  # plan -> request
        self.total_calls = 0        # aggregate NFE: batched network calls

    def submit(self, length: int, prefix: np.ndarray | None = None,
               method: str | None = None) -> int:
        """Enqueue a request; its call schedule is sampled *now*.

        The key and the plan are drawn on the host's CPU device, so a
        submit never waits for the calls queued on the accelerator; the
        key stays there (``Request.key``, uncommitted: a solo replay
        moves it to the default device).  Drawing them is one
        ``scheduler.submit`` span (``request_id``, ``method``,
        ``length``, ``mode``, ``planned_nfe``); the plan's draw is the
        ``engine.plan`` span inside it."""
        if length > self.bucket_len:
            raise ValueError(f"length {length} > bucket_len "
                             f"{self.bucket_len}")
        method = method or self.engine.cfg.method
        spec = self.engine.check_method(method)
        if spec.stepwise_step is None:
            raise ValueError(
                f"{method} does not support continuous batching "
                "(no stepwise_step); submit it to BatchScheduler instead")
        self._rid += 1
        if prefix is not None:
            prefix = np.asarray(prefix, np.int32).reshape(-1)
        r = Request(self._rid, length, prefix, method)
        r.request_id = mint_request_id()
        with obs.span("scheduler.submit", request_id=r.request_id,
                      method=method, length=length,
                      mode="continuous") as sp:
            with jax.default_device(host_device()):
                r.key = jax.random.fold_in(self._key, self._rid)
            # stamp the trace identity onto the plan: the StepwiseRunner
            # reads it back to label every batched call this request rides
            r.plan = dataclasses.replace(
                self.engine.plan_request(r.key, self.bucket_len, method),
                request_id=r.request_id)
            r.t_submit = time.time()
            sp.set(planned_nfe=r.plan.nfe)
        self.queue.append(r)
        return self._rid

    @staticmethod
    def _group(r: Request) -> tuple:
        return (r.method, 0 if r.prefix is None else len(r.prefix))

    def _runner(self, group: tuple) -> StepwiseRunner:
        if group not in self._runners:
            method, prefix_len = group
            self._runners[group] = self.engine.stepwise(
                self.max_batch, self.bucket_len, method,
                prefix_len=prefix_len)
        return self._runners[group]

    def _admit(self, group: tuple) -> None:
        """Move queued requests of ``group`` into its free rows."""
        runner = self._runner(group)
        free = runner.free_rows()
        if not free:
            return
        midflight = bool(runner.active_rows())
        take: list[Request] = []
        rest: list[Request] = []
        for r in self.queue:        # one pass, FIFO within the group
            if self._group(r) == group and len(take) < len(free):
                take.append(r)
            else:
                rest.append(r)
        self.queue = rest
        placed = list(zip(free, take))
        runner.admit_many(
            [(row, r.plan) for row, r in placed],
            [r.prefix for _, r in placed] if group[1] else None)
        runner.padded_positions += sum(self.bucket_len - r.length
                                       for _, r in placed)
        t_admit = time.time()
        for row, r in placed:
            self._row_req[(group, row)] = r
            r.t_admit = t_admit
            if obs.enabled():
                obs.histogram("scheduler.queue_latency_seconds").observe(
                    r.t_admit - r.t_submit, mode="continuous")
                obs.event("scheduler.admit", request_id=r.request_id,
                          method=r.method, mode="continuous", row=row,
                          midflight=midflight,
                          queue_s=r.t_admit - r.t_submit)
                if midflight:
                    obs.counter("scheduler.admissions_midflight").inc(
                        method=r.method)

    def _next_group(self) -> tuple | None:
        """The next group with work, round-robin from the cursor.

        Work = live or unread rows in the group's runner or queued
        requests of the group.  New groups join the rotation in
        first-arrival order; the cursor only ever advances one served
        group at a time, so no group with work is passed over twice
        before every other one is served — the fairness bound a steady
        single-method stream used to violate by pinning the old
        ``self._current`` forever.
        """
        for r in self.queue:
            g = self._group(r)
            if g not in self._rotation:
                self._rotation.append(g)
        n = len(self._rotation)
        for off in range(n):
            g = self._rotation[(self._rr + off) % n]
            runner = self._runners.get(g)
            if ((runner is not None
                 and (runner.active_rows() or runner.unread_rows()))
                    or any(self._group(r) == g for r in self.queue)):
                self._rr = (self._rr + off + 1) % n
                return g
        return None

    def pump(self) -> bool:
        """Serve ONE group: admit what fits, issue one batched call.

        Returns True while work remains (queued, in flight or unread).
        Drive it from a serving loop interleaved with ``submit()`` calls;
        ``run()`` below pumps to completion for synchronous use.

        A request completes on the pump after its last call, whichever
        group that pump serves: once the pump's call is dispatched, the
        canvases every other runner holds unread are read too.  At a
        drain the last pump dispatches no call and only reads.

        Inside the ``scheduler.pump`` span the runner's ``engine.admit``,
        ``engine.stepwise`` and ``engine.harvest`` spans say where the
        host's time goes; what is left is the scheduler's bookkeeping.
        """
        group = self._next_group()
        if group is None:
            return False
        with obs.span("scheduler.pump", method=group[0],
                      prefix_len=group[1]) as sp:
            self._admit(group)
            runner = self._runner(group)
            if obs.enabled():
                obs.gauge("scheduler.queue_depth").set(len(self.queue))
                obs.histogram("scheduler.occupancy").observe(
                    len(runner.active_rows()) / runner.rows,
                    method=group[0])
                sp.set(queue_depth=len(self.queue),
                       live_rows=len(runner.active_rows()))
            calls = runner.calls
            finished = runner.step()
            if runner.calls > calls:
                self.total_calls += 1
                self._retire(group, runner)
                for other in self._runners.values():
                    if other is not runner and other.unread_rows():
                        finished.update(other.harvest())
            t_done = time.time()
            for plan, toks in finished.items():
                r = self._retired.pop(plan)
                r.result = toks[: r.length]
                r.nfe = r.plan.nfe
                r.steps_executed = r.plan.steps_executed
                r.steps_skipped = r.plan.steps_skipped
                r.t_done = t_done
                if obs.enabled():
                    obs.counter("scheduler.steps_skipped").inc(
                        r.steps_skipped, method=r.method)
                    obs.counter("scheduler.requests").inc(method=r.method)
                    obs.histogram("scheduler.service_seconds").observe(
                        t_done - r.t_admit, mode="continuous")
                    obs.event("scheduler.complete",
                              request_id=r.request_id, method=r.method,
                              mode="continuous", nfe=r.nfe,
                              steps_skipped=r.steps_skipped,
                              service_s=t_done - r.t_admit)
                    slo_lib.observe_request(
                        r.method, latency_s=t_done - r.t_admit,
                        queue_s=r.t_admit - r.t_submit, nfe=r.nfe)
                self.done[r.rid] = r
        return bool(self.queue or self._row_req or self._retired)

    def _retire(self, group: tuple, runner: StepwiseRunner) -> None:
        """Free the rows of ``group`` whose last call was just dispatched:
        their requests wait for their canvas under their plan, and their
        padding leaves the runner's count."""
        live = set(runner.active_rows())
        for key in [k for k in self._row_req
                    if k[0] == group and k[1] not in live]:
            r = self._row_req.pop(key)
            runner.padded_positions -= self.bucket_len - r.length
            self._retired[r.plan] = r

    def run(self) -> dict[int, Request]:
        """Pump to completion; returns completed requests by id."""
        while self.pump():
            pass
        return self.done
