"""The device half of continuous batching: coalesced engine calls.

``ServeQueue`` joins the pure scheduler (``repro.serve.scheduler``) to the
compiled prefill/decode halves from ``repro.dist.step``:

  * **ragged batching** — a :class:`~repro.serve.scheduler.Group`'s live
    requests are stacked on the batch axis and zero-padded to the engine's
    batch-block grid (``scheduler.padded_batch``, the pure mirror of
    ``kernels/engine.py``); batch rows are independent in every model
    family, so padding is exact — the pad rows' outputs are simply dropped
    (``tests/test_serve_batching.py`` pins coalesced == sequential).
  * **warm executor pool** — :class:`ExecutorPool` builds and caches one
    ``(prefill_fn, serve_fn)`` pair per ``(padded_batch, prompt_len,
    max_len)`` shape bucket, so steady-state traffic never pays a jit
    compile; ``warm()`` pays them before traffic (the compiled-function
    analogue of the tuner's plan-cache ``prewarm``).
  * **two clocks** — scheduling decisions run on the injectable ``clock``
    (virtual in the deterministic load benchmark), latency *accounting*
    always on the wall clock: per-request ``serve.request_us`` /
    ``serve.ttft_us`` / ``serve.prefill_us`` / ``serve.decode_token_us``
    obs histograms plus ``serve.queue_depth`` / ``serve.in_flight`` gauges
    and ``serve.rejected`` / ``serve.evicted`` counters.
  * **resilience** — every engine call passes the ``serve.prefill`` /
    ``serve.step`` fault points and retries with backoff under the
    degraded-mode accounting PR 8 introduced (docs/robustness.md).

Sampling is host-side and *batch-composition independent*: greedy argmax,
or for ``temperature > 0`` a per-request Gumbel draw seeded by
``(seed, rid, token_index)`` — the same request yields the same tokens
whether it rode a coalesced batch or ran alone, which is what makes the
parity tests (and cross-mode benchmark comparisons) meaningful.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from .scheduler import (G_DONE, Decode, Group, Prefill, Scheduler,
                        SchedulerConfig, padded_batch)
from .session import DONE, Request, make_request

__all__ = ["ServeQueue", "ExecutorPool", "pad_cache", "sample_token",
           "DEFAULT_LEN_QUANTUM"]

# Decode-capacity quantum: a group's cache length is its prompt plus
# max_gen rounded up to this, so nearby generation budgets share one
# compiled (batch, prompt, max_len) executor instead of each paying a jit.
DEFAULT_LEN_QUANTUM = 8


def pad_cache(cfg, cache, max_len: int):
    """Grow the prefill cache's sequence axis to ``max_len`` (headroom for
    decode).  Window-capped and state caches are already final-size."""
    import jax
    import jax.numpy as jnp

    def leaf(path, x):
        names = [getattr(k, "key", str(k)) for k in path]
        if names[-1] in ("k", "v") and x.ndim == 5:
            cap = max_len
            if cfg.sliding_window:
                cap = min(max_len, cfg.sliding_window)
            if x.shape[2] < cap:
                pad = [(0, 0)] * 5
                pad[2] = (0, cap - x.shape[2])
                return jnp.pad(x, pad)
        return x
    return jax.tree_util.tree_map_with_path(leaf, cache)


def sample_token(logits_row: np.ndarray, *, temperature: float, seed: int,
                 rid: int, index: int) -> int:
    """Sample one token from a single request's logits row.

    Greedy at ``temperature <= 0``; otherwise a Gumbel-max draw whose
    randomness is a pure function of ``(seed, rid, index)`` — never of the
    batch the row rode in — so batched and sequential execution of the same
    request emit identical streams (the parity contract)."""
    row = np.asarray(logits_row, np.float64)
    if temperature <= 0:
        return int(np.argmax(row))
    rng = np.random.default_rng([abs(int(seed)), int(rid), int(index)])
    u = rng.random(row.shape[0])
    gumbel = -np.log(-np.log(u + 1e-20) + 1e-20)
    return int(np.argmax(row / temperature + gumbel))


@dataclasses.dataclass
class _Bundle:
    """One compiled shape cell: ``(padded_batch, prompt_len, max_len)``."""

    prefill_fn: Callable
    serve_fn: Callable
    batch: int
    prompt_len: int
    max_len: int            # prompt + decode capacity (pre-frontend-prefix)
    extra_prefix: int       # vision patch prefix shifting absolute positions
    extras: Dict[str, Any]  # frontend stub inputs for this batch size


class ExecutorPool:
    """Build-once cache of jitted prefill/decode pairs per shape bucket.

    The serving analogue of the tuner's warm plan cache: a bucket is built
    (and optionally :meth:`warm`\\ ed — compiled *and* executed once) ahead
    of traffic, after which every group landing in it is dispatch-only.
    """

    def __init__(self, cfg, mesh, params, *, obs=None, recorder=None):
        self.cfg = cfg
        self.mesh = mesh
        self.params = params
        self.obs = obs
        self.recorder = recorder
        self._bundles: Dict[Tuple[int, int, int], _Bundle] = {}
        self.builds = 0

    def _extras(self, batch: int) -> Dict[str, Any]:
        from ..models import frontends
        cfg = self.cfg
        if cfg.frontend == "vision_stub":
            return {"patches": frontends.vision_patches_stub(cfg, batch)}
        if cfg.frontend == "audio_stub":
            return {"frames": frontends.audio_frames_stub(cfg, batch)}
        return {}

    def bundle(self, batch: int, prompt_len: int, max_len: int) -> _Bundle:
        key = (batch, prompt_len, max_len)
        hit = self._bundles.get(key)
        if hit is not None:
            return hit
        import jax
        import jax.numpy as jnp

        from ..dist import step as step_lib
        from ..models import api

        cfg = self.cfg
        extras = self._extras(batch)
        pav = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), self.params)
        bav = {"tokens": jax.ShapeDtypeStruct((batch, prompt_len),
                                              jnp.int32)}
        bav.update({k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                    for k, v in extras.items()})
        prefill_fn, _, _ = step_lib.build_prefill(
            cfg, self.mesh, pav, bav, obs=self.obs, recorder=self.recorder)
        extra = cfg.num_patches if cfg.frontend == "vision_stub" else 0
        cache_avals, _ = jax.eval_shape(
            lambda p, b: api.prefill(cfg, p, b), pav, bav)
        padded_avals = jax.eval_shape(
            lambda c: pad_cache(cfg, c, max_len + extra), cache_avals)
        serve_fn, _, _ = step_lib.build_serve_step(
            cfg, self.mesh, pav, padded_avals, obs=self.obs,
            recorder=self.recorder)
        b = _Bundle(prefill_fn=prefill_fn, serve_fn=serve_fn, batch=batch,
                    prompt_len=prompt_len, max_len=max_len,
                    extra_prefix=extra, extras=extras)
        self._bundles[key] = b
        self.builds += 1
        return b

    def warm(self, shapes: Sequence[Tuple[int, int, int]]) -> int:
        """Compile AND execute each ``(batch, prompt_len, max_len)`` cell
        once on dummy tokens, so the first real request in the bucket pays
        dispatch cost only.  Returns the number of cells warmed."""
        import jax
        import jax.numpy as jnp
        n = 0
        for batch, prompt_len, max_len in dict.fromkeys(shapes):
            b = self.bundle(padded_batch(batch), prompt_len, max_len)
            toks = jnp.zeros((b.batch, b.prompt_len), jnp.int32)
            cache, logits = b.prefill_fn(self.params,
                                         {"tokens": toks, **b.extras})
            cache = pad_cache(self.cfg, cache, b.max_len + b.extra_prefix)
            step_toks = jnp.zeros((b.batch, 1), jnp.int32)
            pos = jnp.int32(b.prompt_len + b.extra_prefix)
            cache, logits = b.serve_fn(self.params, cache, step_toks, pos)
            jax.block_until_ready(logits)
            n += 1
        return n

    def __len__(self) -> int:
        return len(self._bundles)


@dataclasses.dataclass
class _GroupRuntime:
    """Device-side state of an in-flight group between engine calls."""

    bundle: _Bundle
    cache: Any
    toks: Any               # (padded_batch, 1) int32 — next step's inputs
    pos0: int               # absolute position of the first decode write


class ServeQueue:
    """Continuous-batching front end over the compiled serving halves."""

    def __init__(self, cfg, mesh, params, *,
                 scheduler: Optional[Scheduler] = None,
                 config: Optional[SchedulerConfig] = None,
                 pool: Optional[ExecutorPool] = None,
                 obs=None, recorder=None,
                 clock: Callable[[], float] = time.perf_counter,
                 temperature: float = 0.0, seed: int = 0,
                 len_quantum: int = DEFAULT_LEN_QUANTUM,
                 retry_kw: Optional[Dict[str, Any]] = None,
                 record_logits: bool = False):
        if scheduler is not None and config is not None:
            raise ValueError("pass scheduler= or config=, not both")
        self.cfg = cfg
        self.params = params
        self.sched = scheduler or Scheduler(config)
        # NB: not `pool or ...` — an empty ExecutorPool is falsy (__len__)
        self.pool = pool if pool is not None else \
            ExecutorPool(cfg, mesh, params, obs=obs, recorder=recorder)
        self.obs = obs
        self.clock = clock
        self.temperature = float(temperature)
        self.seed = int(seed)
        self.len_quantum = max(int(len_quantum), 1)
        self.retry_kw = dict(retry_kw) if retry_kw else {"retries": 0}
        self.record_logits = record_logits
        self.logits_log: Dict[int, List[np.ndarray]] = {}
        self.completed: List[Request] = []
        self._rt: Dict[int, _GroupRuntime] = {}
        self._seen = {k: 0 for k in ("rejected", "evicted")}

    # -- obs plumbing --------------------------------------------------------

    def _hist(self, name: str):
        return self.obs.histogram(name) if self.obs is not None else None

    def _observe(self, name: str, us: float, n: int = 1) -> None:
        h = self._hist(name)
        if h is not None:
            for _ in range(max(n, 1)):
                h.observe(us)

    def _sync_counters(self) -> None:
        """Mirror scheduler-side sheds/evictions into obs counters (delta
        sync: the scheduler is obs-free by design) and refresh gauges."""
        if self.obs is None:
            return
        for key, metric in (("rejected", "serve.rejected"),
                            ("evicted", "serve.evicted")):
            delta = self.sched.counters[key] - self._seen[key]
            if delta > 0:
                self.obs.counter(metric).inc(delta)
                self._seen[key] = self.sched.counters[key]
        self.obs.gauge("serve.queue_depth").set(self.sched.queue_depth)
        self.obs.gauge("serve.in_flight").set(self.sched.in_flight)

    # -- submission ----------------------------------------------------------

    def submit(self, prompt: Sequence[int], gen_len: int, *,
               deadline_s: Optional[float] = None,
               now: Optional[float] = None,
               rid: Optional[int] = None) -> Request:
        """Admit one request (or shed it: ``req.state == REJECTED``).

        ``now`` defaults to the scheduling clock; pass an explicit value
        when driving a virtual timeline.  ``deadline_s`` is absolute on
        that same clock.  ``rid`` pins the request id — the sampling stream
        is keyed on ``(seed, rid, token_index)``, so pinning it makes the
        same request reproducible across queues (the parity tests run one
        request through a batched and a sequential queue and compare token
        streams).
        """
        now = self.clock() if now is None else now
        req = make_request(prompt=prompt, gen_len=gen_len, now=now,
                           deadline_s=deadline_s, rid=rid)
        req.wall_arrival_s = time.perf_counter()
        self.sched.submit(req, now)
        self._sync_counters()
        return req

    # -- group execution -----------------------------------------------------

    def _max_len(self, group: Group) -> int:
        q = self.len_quantum
        return group.prompt_len + -(-group.max_gen // q) * q

    def _sample_rows(self, logits: np.ndarray, group: Group,
                     index_of: Callable[[Request], int]) -> np.ndarray:
        """Next-token column for every slot; live rows sample per-request,
        pad rows (whose outputs are discarded) take the argmax.  Only the
        first ``vocab_size`` logits are tokens: the unembedding is padded
        to ``cfg.vocab_padded()`` and its padding rows are never sampled."""
        logits = logits[:, :self.cfg.vocab_size]
        toks = np.zeros((logits.shape[0], 1), np.int32)
        for i in range(logits.shape[0]):
            if i < group.size:
                r = group.requests[i]
                toks[i, 0] = sample_token(
                    logits[i], temperature=self.temperature, seed=self.seed,
                    rid=r.rid, index=index_of(r))
            else:
                toks[i, 0] = int(np.argmax(logits[i]))
        return toks

    def _run_prefill(self, group: Group, now: float) -> List[Request]:
        import jax
        import jax.numpy as jnp

        from ..resilience.fallback import retry_with_backoff
        from ..resilience.inject import fault_point

        bundle = self.pool.bundle(group.padded_size, group.prompt_len,
                                  self._max_len(group))
        tokens = np.zeros((group.padded_size, group.prompt_len), np.int32)
        for i, r in enumerate(group.requests):
            tokens[i] = np.asarray(r.prompt, np.int32)
        batch = {"tokens": jnp.asarray(tokens), **bundle.extras}

        def call():
            # the fault point fires BEFORE the jitted call, so a retried
            # prefill never reuses a consumed buffer
            fault_point("serve.prefill")
            return bundle.prefill_fn(self.params, batch)

        t0 = time.perf_counter()
        cache, logits = retry_with_backoff(call, **self.retry_kw)
        jax.block_until_ready(logits)
        dt_us = (time.perf_counter() - t0) * 1e6
        cache = pad_cache(self.cfg, cache,
                          bundle.max_len + bundle.extra_prefix)
        logits_np = np.asarray(logits)
        wall = time.perf_counter()
        toks = self._sample_rows(logits_np, group, lambda r: 0)
        for i, r in enumerate(group.requests):
            r.tokens.append(int(toks[i, 0]))
            r.wall_first_token_s = wall
            if self.record_logits:
                self.logits_log.setdefault(r.rid, []).append(
                    logits_np[i].copy())
        # Every rider experienced the coalesced call's latency — one
        # observation per request, the accounting admission control reads.
        self._observe("serve.prefill_us", dt_us, group.size)
        if self.obs is not None:
            self.obs.counter("serve.requests").inc(group.size)
            self.obs.counter("serve.prefill_calls").inc()
        finished = self.sched.note_prefill_done(group.gid, now)
        self._note_finished(finished, wall)
        if group.state != G_DONE:
            self._rt[group.gid] = _GroupRuntime(
                bundle=bundle, cache=cache, toks=jnp.asarray(toks),
                pos0=group.prompt_len + bundle.extra_prefix)
        return finished

    def _run_decode(self, group: Group, now: float) -> List[Request]:
        import jax
        import jax.numpy as jnp

        from ..resilience.fallback import retry_with_backoff
        from ..resilience.inject import fault_point

        rt = self._rt[group.gid]
        pos = jnp.int32(rt.pos0 + group.steps_done)

        def call(cache, toks, pos):
            fault_point("serve.step")
            return rt.bundle.serve_fn(self.params, cache, toks, pos)

        was_active = list(group.active_requests)
        t0 = time.perf_counter()
        cache, logits = retry_with_backoff(call, rt.cache, rt.toks, pos,
                                           **self.retry_kw)
        jax.block_until_ready(logits)
        dt_us = (time.perf_counter() - t0) * 1e6
        logits_np = np.asarray(logits)
        wall = time.perf_counter()
        step_index = group.steps_done + 1   # token index this step emits
        toks = self._sample_rows(logits_np, group, lambda r: step_index)
        for i, r in enumerate(group.requests):
            if r in was_active:
                r.tokens.append(int(toks[i, 0]))
                if self.record_logits:
                    self.logits_log.setdefault(r.rid, []).append(
                        logits_np[i].copy())
        rt.cache, rt.toks = cache, jnp.asarray(toks)
        # per-token decode latency: the step's wall clock is what every
        # still-active rider waited for its next token
        self._observe("serve.decode_token_us", dt_us, len(was_active))
        if self.obs is not None:
            self.obs.counter("serve.decode_calls").inc()
        finished = self.sched.note_decode_done(group.gid, now)
        self._note_finished(finished, wall)
        if group.state == G_DONE:
            self._rt.pop(group.gid, None)
        return finished

    def _note_finished(self, finished: List[Request], wall: float) -> None:
        for r in finished:
            r.wall_finish_s = wall
            if r.state == DONE:
                self.completed.append(r)
                if r.wall_e2e_s is not None:
                    self._observe("serve.request_us", r.wall_e2e_s * 1e6)
                if r.wall_ttft_s is not None:
                    self._observe("serve.ttft_us", r.wall_ttft_s * 1e6)

    # -- the drive loop ------------------------------------------------------

    @property
    def pending(self) -> bool:
        return self.sched.pending

    def step(self, now: Optional[float] = None) -> bool:
        """Run the scheduler's next engine action (one coalesced prefill or
        one decode step); returns False when the engine would idle."""
        now = self.clock() if now is None else now
        action = self.sched.poll(now)
        if action is None:
            self._sync_counters()
            return False
        if isinstance(action, Prefill):
            self._run_prefill(action.group, now)
        elif isinstance(action, Decode):
            self._run_decode(action.group, now)
        self._sync_counters()
        return True

    def drain(self, max_steps: int = 1_000_000) -> List[Request]:
        """Step until idle (bounded by ``max_steps``); returns every
        request completed so far, submission order preserved."""
        steps = 0
        while self.pending and steps < max_steps:
            if not self.step():
                break
            steps += 1
        return list(self.completed)
