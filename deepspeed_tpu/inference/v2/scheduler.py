"""Disaggregated continuous-batching scheduler with SLA-aware admission.

The reference exposes ``put/query/flush`` primitives and leaves the token
budgeting loop to DeepSpeed-MII (SURVEY §3.5; ``engine_v2.py:153,179,228``,
``scheduling_utils.py``). This module provides that serving loop in-repo.

Rebuilt around the ragged-wave engine (ISSUE 6): every wave — any mix of
prefill chunks and decode tokens — is ONE compiled program per
``(tokens, atoms, pages)`` bucket, so the former three-canonical-shapes
restriction (``max_prefills_per_wave=1`` under arrival traffic, forced by
mid-serving compiles of novel decode×prefill×chunk bucket products) is
gone: waves compose freely.

Two serving policies ride on top:

- **Wave composition** (``mode``): ``"mixed"`` is classic Dynamic
  SplitFuse — decode tokens for every running sequence first, remaining
  budget to prefill chunks. ``"disaggregated"`` separates the classes:
  decode-only waves keep inter-token latency flat (no decode ever waits
  behind a long prefill row), prefill-only waves interleave at a share set
  by SLA pressure. ``"auto"`` picks disaggregated when either SLA target
  is set, mixed otherwise.
- **Admission** (``ttft_sla_s`` / ``gen_sla_tok_s``): NEW prefills are
  admitted greedily until the generation SLA is at risk (rolling p50 wave
  execute time above ``1/gen_sla_tok_s`` — read from the same latency
  reservoir machinery telemetry serves, ``telemetry.metrics
  .LatencyHistogram``); TTFT pressure (oldest queued wait beyond half
  ``ttft_sla_s``) overrides the freeze and raises the prefill share, so
  neither SLA can starve the other unboundedly.

TTFT attribution is split per request: queue wait (submit → first
scheduled) and execute (first scheduled → first token) land in separate
telemetry reservoirs (``record_request``), and wave records carry execute
time only — deep queues can no longer masquerade as slow forwards.
"""

from __future__ import annotations

import dataclasses
import itertools
from typing import Dict, List, Optional, Sequence

import numpy as np

from ...telemetry import clock, get_telemetry, maybe_enable_from_env
from ...telemetry.metrics import LatencyHistogram
from ...telemetry.trace import PHASE_SERVING


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray
    max_new_tokens: int = 64
    temperature: float = 0.0
    eos_token_id: Optional[int] = None
    # state
    prompt_consumed: int = 0
    generated: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    # how many generated tokens have been folded into `prompt` by preemption
    folded: int = 0
    # latency attribution (clock.now() timestamps; None = not yet)
    submit_s: float = 0.0
    first_sched_s: Optional[float] = None
    first_token_s: Optional[float] = None

    @property
    def prefill_remaining(self) -> int:
        return len(self.prompt) - self.prompt_consumed

    @property
    def queue_wait_s(self) -> float:
        return (self.first_sched_s - self.submit_s) \
            if self.first_sched_s is not None else 0.0


class ContinuousBatchingScheduler:

    def __init__(self, engine, token_budget: Optional[int] = None, seed: int = 0,
                 max_prefills_per_wave: Optional[int] = None,
                 kv_host_offload: bool = True,
                 mode: str = "auto",
                 ttft_sla_s: Optional[float] = None,
                 gen_sla_tok_s: Optional[float] = None):
        self.engine = engine
        # serving telemetry (queue depth, occupancy, per-token latency
        # percentiles): the process-global recorder — a NULL object unless
        # an engine configured it or DSTPU_TELEMETRY=1
        maybe_enable_from_env()
        self.token_budget = token_budget or engine.config.state_manager.max_ragged_batch_size
        # preemption stashes KV to host RAM (engine.offload_sequence) and
        # resumes by restore — no re-prefill. False restores the old
        # flush-and-recompute behavior.
        self.kv_host_offload = (kv_host_offload
                                and hasattr(engine, "offload_sequence"))
        self._offloaded: List[Request] = []
        # an admission cap, no longer a compile-count guard: the ragged
        # wave program serves any composition from a handful of
        # (tokens, atoms, pages) buckets (ISSUE 6 dropped the
        # three-canonical-shapes restriction this knob used to enforce)
        self.max_prefills_per_wave = max_prefills_per_wave or (1 << 30)
        if mode not in ("auto", "mixed", "disaggregated"):
            raise ValueError(f"mode must be auto|mixed|disaggregated, "
                             f"got {mode!r}")
        self.ttft_sla_s = ttft_sla_s
        self.gen_sla_tok_s = gen_sla_tok_s
        self.mode = ("disaggregated" if (ttft_sla_s or gen_sla_tok_s)
                     else "mixed") if mode == "auto" else mode
        # rolling wave-EXECUTE reservoir driving admission — the same
        # bounded-reservoir machinery as the telemetry serving metrics,
        # held locally so the policy works with telemetry off
        self._exec_hist = LatencyHistogram(cap=128)
        self._pf_credit = 0.0   # disaggregated prefill-wave accumulator
        self._uid_gen = itertools.count(1)
        self._queue: List[Request] = []       # waiting for / mid prefill
        self._running: List[Request] = []     # generating
        self._rng = np.random.default_rng(seed)

    # -- client API ---------------------------------------------------------
    def submit(self, prompt: Sequence[int], max_new_tokens: int = 64,
               temperature: float = 0.0, eos_token_id: Optional[int] = None) -> Request:
        max_ctx = getattr(self.engine, "max_context", None)
        if max_ctx is not None and len(prompt) >= max_ctx:
            raise ValueError(f"prompt of {len(prompt)} tokens cannot fit the "
                             f"engine's max context of {max_ctx}")
        req = Request(uid=next(self._uid_gen), prompt=np.asarray(prompt, np.int32),
                      max_new_tokens=max_new_tokens, temperature=temperature,
                      eos_token_id=eos_token_id, submit_s=clock.now())
        self._queue.append(req)
        return req

    @property
    def has_work(self) -> bool:
        return bool(self._queue or self._running or self._offloaded)

    def _sample(self, req: Request, logits: np.ndarray) -> int:
        if req.temperature <= 0.0:
            return int(np.argmax(logits))
        z = logits / max(req.temperature, 1e-6)
        z = z - z.max()
        p = np.exp(z) / np.exp(z).sum()
        return int(self._rng.choice(len(p), p=p))

    def _finish(self, req: Request) -> None:
        req.done = True
        self.engine.flush(req.uid)

    def _preempt(self, req: Request) -> None:
        """KV pressure. Preferred path: page the sequence's KV blocks to
        host RAM (BlockedKVCache.offload — the capability the reference
        stubs at kv_cache.py:169) and resume later with one H2D scatter.
        Fallback (kv_host_offload=False): drop the cache and requeue for
        re-prefill of prompt + everything generated so far."""
        if self.kv_host_offload:
            max_ctx = getattr(self.engine, "max_context", None)
            ctx = len(req.prompt) + len(req.generated) - req.folded
            if max_ctx is not None and ctx + 1 >= max_ctx:
                # context capacity reached: offloading would thrash a full
                # D2H+H2D of the KV every step with no way to ever decode
                # another token — end generation (same terminal rule as the
                # flush path below)
                self._finish(req)
                self._running.remove(req)
                return
            self.engine.offload_sequence(req.uid)
            self._running.remove(req)
            self._offloaded.append(req)
            return
        self.engine.flush(req.uid)
        self._running.remove(req)
        # fold only the not-yet-folded tail: a second preemption must not
        # duplicate tokens already moved into the prompt
        fresh = req.generated[req.folded:]
        req.prompt = np.concatenate([req.prompt, np.asarray(fresh, np.int32)])
        req.folded = len(req.generated)
        req.prompt_consumed = 0
        max_ctx = getattr(self.engine, "max_context", None)
        if max_ctx is not None and len(req.prompt) >= max_ctx:
            # context capacity reached — generation ends here (its KV is
            # already flushed); requeueing would head-of-line block forever
            req.done = True
            return
        self._queue.insert(0, req)

    def _restore_offloaded(self) -> int:
        """Re-place stashed sequences whose KV fits again; returns how
        many. Headroom 1 block prevents restore->preempt thrash; when
        nothing else holds blocks, restore unconditionally (no one to
        wait for)."""
        n = 0
        for req in list(self._offloaded):
            headroom = 1 if (self._running or self._queue) else 0
            if self.engine.can_restore(req.uid, headroom=headroom):
                self.engine.restore_sequence(req.uid)
                self._offloaded.remove(req)
                self._running.append(req)
                n += 1
        return n

    # -- SLA policy ---------------------------------------------------------
    def _exec_p50(self) -> float:
        if not len(self._exec_hist):
            return 0.0
        return self._exec_hist.percentiles((50,))["p50"]

    def _gen_pressure(self) -> bool:
        """Generation SLA at risk: rolling p50 wave execute above the
        per-token latency the SLA allows (a running sequence gains at
        most one token per wave)."""
        if not self.gen_sla_tok_s or not self._running:
            return False
        p50 = self._exec_p50()
        return p50 > 0.0 and p50 > 1.0 / self.gen_sla_tok_s

    def _ttft_pressure(self, now: float) -> bool:
        """TTFT SLA at risk: the oldest queued NOT-YET-SCHEDULED request
        has burned half its budget waiting."""
        if not self.ttft_sla_s:
            return False
        waits = [now - r.submit_s for r in self._queue
                 if r.first_sched_s is None]
        return bool(waits) and max(waits) > 0.5 * self.ttft_sla_s

    def _admit_new(self, now: float) -> bool:
        """Whether NEW requests (nothing prefilled yet) may enter this
        wave. Continuing chunked prefills are always admitted — they
        already hold KV blocks; stalling them wastes pool. Gen pressure
        freezes admission; TTFT pressure overrides the freeze (triage:
        both SLAs degrade gracefully, neither starves unboundedly)."""
        if not self._gen_pressure():
            return True
        return self._ttft_pressure(now)

    def _wave_kind(self, now: float) -> str:
        """Disaggregation: 'mixed' | 'decode' | 'prefill'. Degenerates to
        whatever work exists when only one class is pending."""
        has_p = bool(self._queue)
        has_d = bool(self._running)
        if self.mode != "disaggregated" or not (has_p and has_d):
            return "mixed"
        # prefill share: every other wave by default; TTFT pressure makes
        # every wave a prefill wave until relieved, gen pressure drops it
        # to one in four
        share = 0.5
        if self._ttft_pressure(now):
            share = 1.0
        elif self._gen_pressure():
            share = 0.25
        self._pf_credit += share
        if self._pf_credit >= 1.0:
            self._pf_credit -= 1.0
            return "prefill"
        return "decode"

    # -- one engine step ----------------------------------------------------
    def _try_decode_burst(self):
        """When ONLY decodes are pending, fuse K tokens per sequence into
        one dispatch with on-device sampling (engine ``decode_burst``) —
        the serving loop's answer to per-dispatch round-trip latency.
        Prefill work pending disables bursting so TTFT never waits behind
        a burst. Returns (tokens processed, burst depth k, uids); tokens 0
        = not applicable."""
        k_cfg = getattr(self.engine.config, "decode_burst", 1)
        if self._queue or not self._running or k_cfg <= 1:
            return 0, 0, []
        tele = get_telemetry()
        with tele.phase("sched.compose", phase=PHASE_SERVING) as span:
            reqs, uids, k = self._compose_burst(k_cfg)
            if tele.enabled:
                span.req = uids
        if k < 2:
            # KV pressure (or nothing to fuse): let the single-token path
            # run — it preempts one sequence at a time
            return 0, 0, []
        toks = self.engine.decode_burst(
            uids, [r.generated[-1] for r in reqs], k,
            temperatures=[r.temperature for r in reqs],
            seed=int(self._rng.integers(1 << 31)))
        with tele.phase("sched.sample", phase=PHASE_SERVING, req=uids):
            for r, row in zip(reqs, toks):
                for tok in row:
                    r.generated.append(int(tok))
                    if ((r.eos_token_id is not None and tok == r.eos_token_id)
                            or len(r.generated) >= r.max_new_tokens):
                        # overshoot tokens past EOS are discarded here; the
                        # sequence's KV is flushed with the request
                        self._finish(r)
                        self._running.remove(r)
                        break
        return len(reqs) * k, k, uids

    def _compose_burst(self, k_cfg: int):
        """The burst's members and depth: (requests, uids, k); k < 2 means
        no burst fits."""
        # pick the burst depth k maximizing fused tokens k * |{remaining>=k}|
        # and burst only that subset: a single nearly-done request must not
        # force everyone down to single-token steps (the tail would pay a
        # full dispatch round trip per token)
        remaining = {r.uid: r.max_new_tokens - len(r.generated)
                     for r in self._running}
        # powers of two only: every distinct k is a separately compiled
        # program, so the candidate set must stay tiny
        candidates = []
        k = 2
        while k <= k_cfg:
            n = sum(1 for v in remaining.values() if v >= k)
            if n:
                candidates.append((k * n, k))
            k *= 2
        # best fused-token count first; if KV cannot host that k, retry the
        # next candidate rather than silently giving up bursting entirely
        reqs, uids, k = [], [], 0
        for _, cand_k in sorted(candidates, reverse=True):
            cand_reqs = [r for r in self._running
                         if remaining[r.uid] >= cand_k]
            cand_uids = [r.uid for r in cand_reqs]
            if self.engine.can_burst(cand_uids, cand_k):
                reqs, uids, k = cand_reqs, cand_uids, cand_k
                break
        return reqs, uids, k

    def step(self, _retry: bool = True) -> int:
        """Run one composed wave; returns tokens processed. One
        ``sched.step`` span with the wave's uids; its children
        (``sched.restore``, ``sched.compose``, the engine's ``wave.build``
        / ``wave.dispatch`` / ``wave.fetch``, ``sched.sample``) say where
        the host spent it (docs/OBSERVABILITY.md)."""
        tele = get_telemetry()
        with tele.phase("sched.step", phase=PHASE_SERVING) as span:
            return self._step(tele, span, _retry)

    def _step(self, tele, step_span, _retry: bool) -> int:
        _w0 = clock.now()
        # restore offloaded sequences as KV pressure relents — they were
        # running before anything queued, so they outrank new prefills
        with tele.phase("sched.restore", phase=PHASE_SERVING):
            self._restore_offloaded()
        burst, burst_k, burst_uids = self._try_decode_burst()
        if burst:
            dur = clock.now() - _w0
            # the admission policy reads this reservoir as "time per
            # decode token per sequence"; a burst wave carries k tokens
            # per sequence, so normalize or gen-pressure fires k x early
            self._exec_hist.record(dur / max(burst_k, 1))
            if tele.enabled:
                step_span.req = burst_uids
                tele.record_wave(
                    "burst", tokens=burst, duration_s=dur,
                    queue_depth=len(self._queue), running=len(self._running),
                    occupancy=burst / max(self.token_budget, 1),
                    counters=self._engine_counters())
            return burst
        with tele.phase("sched.compose", phase=PHASE_SERVING) as span:
            (kind_plan, uids, tokens, decode_reqs, prefill_reqs,
             admitted) = self._compose(_w0)
            if tele.enabled:
                span.req = step_span.req = uids
        if not uids:
            # a disaggregated single-class wave may compose empty (KV
            # full / admission frozen on a prefill wave; every running
            # sequence preempted on a decode wave): fall back to ONE
            # mixed wave so the other class still drains rather than
            # reporting a bogus deadlock to the driver
            if kind_plan != "mixed" and (self._running or self._queue
                                         or self._offloaded):
                self._pf_credit = 0.0
                return self._step_mixed_fallback(_retry)
            # a preempt during decode budgeting may have just freed the
            # blocks an offloaded sequence needs — drivers treat 0 as
            # deadlock, so retry ONCE after a restore pass rather than
            # abandoning restorable work (single retry: a genuinely wedged
            # pool must still return 0)
            if _retry and self._offloaded and self._restore_offloaded():
                return self.step(_retry=False)
            return 0

        logits = self.engine.put(uids, tokens)
        dur = clock.now() - _w0
        self._exec_hist.record(dur)
        if tele.enabled:
            n_tokens = sum(len(t) for t in tokens)
            kind = ("mixed" if decode_reqs and prefill_reqs
                    else "decode" if decode_reqs else "prefill")
            tele.record_wave(
                kind, tokens=n_tokens, duration_s=dur,
                queue_depth=len(self._queue), running=len(self._running),
                occupancy=n_tokens / max(self.token_budget, 1),
                admitted=len(admitted),
                queue_wait_s=max((r.queue_wait_s for r in admitted),
                                 default=0.0),
                counters=self._engine_counters())
        with tele.phase("sched.sample", phase=PHASE_SERVING, req=uids):
            self._consume(tele, uids, tokens, logits, decode_reqs,
                          prefill_reqs)
        return sum(len(t) for t in tokens)

    def _engine_counters(self) -> Optional[dict]:
        """What the engine counted in the dispatches of this step."""
        return getattr(self.engine, "last_counters", None)

    def _compose(self, _w0: float):
        """Admission and budgeting of one wave: (kind planned, uids,
        token chunks, decode requests, prefill requests, newly admitted)."""
        kind_plan = self._wave_kind(_w0)
        uids: List[int] = []
        tokens: List[np.ndarray] = []
        decode_reqs: List[Request] = []
        budget = self.token_budget

        # 1. decode tokens for running sequences (highest priority — keeps
        #    generation latency EMA stable, the reference's SLA framing) —
        #    unless this is a disaggregated PREFILL wave.
        #    Decodes are budgeted through can_schedule too: crossing a KV
        #    block boundary with no free blocks must preempt, not crash put()
        if kind_plan != "prefill":
            for req in list(self._running):
                if budget <= 0:
                    break
                if not self.engine.can_schedule(uids + [req.uid],
                                                [len(t) for t in tokens] + [1]):
                    self._preempt(req)
                    continue
                nxt = req.generated[-1]
                uids.append(req.uid)
                tokens.append(np.asarray([nxt], np.int32))
                decode_reqs.append(req)
                budget -= 1

        # 2. remaining budget → prefill chunks, FIFO (skipped entirely on
        #    disaggregated decode waves; new-request admission gated by
        #    the SLA policy)
        prefill_reqs: List[Request] = []
        admitted: List[Request] = []
        if kind_plan != "decode":
            admit_new = self._admit_new(_w0)
            for req in self._queue:
                if budget <= 0 or len(prefill_reqs) >= self.max_prefills_per_wave:
                    break
                if req.first_sched_s is None and not admit_new:
                    break  # FIFO: later arrivals must not jump the freeze
                take = min(budget, req.prefill_remaining)
                chunk = req.prompt[req.prompt_consumed:req.prompt_consumed + take]
                if not self.engine.can_schedule(uids + [req.uid],
                                                [len(t) for t in tokens] + [take]):
                    break
                if req.first_sched_s is None:
                    req.first_sched_s = clock.now()
                    admitted.append(req)
                uids.append(req.uid)
                tokens.append(chunk)
                prefill_reqs.append(req)
                budget -= take
        return kind_plan, uids, tokens, decode_reqs, prefill_reqs, admitted

    def _consume(self, tele, uids, tokens, logits, decode_reqs,
                 prefill_reqs) -> None:
        """Sample from the wave's logits and move requests along."""
        by_uid: Dict[int, np.ndarray] = dict(zip(uids, logits))

        for req in decode_reqs:
            tok = self._sample(req, by_uid[req.uid])
            req.generated.append(tok)
            if ((req.eos_token_id is not None and tok == req.eos_token_id)
                    or len(req.generated) >= req.max_new_tokens):
                self._finish(req)
                self._running.remove(req)

        for req in prefill_reqs:
            req.prompt_consumed += len(tokens[uids.index(req.uid)])
            if req.prefill_remaining == 0:
                tok = self._sample(req, by_uid[req.uid])
                req.generated.append(tok)
                if req.first_token_s is None:
                    req.first_token_s = clock.now()
                    tele.record_request(req.queue_wait_s,
                                        req.first_token_s - req.submit_s)
                self._queue.remove(req)
                # len() check, not ==1: a preempted request resumes prefill
                # with part of its generation budget already spent
                if ((req.eos_token_id is not None and tok == req.eos_token_id)
                        or len(req.generated) >= req.max_new_tokens):
                    self._finish(req)
                else:
                    self._running.append(req)

    def _step_mixed_fallback(self, _retry: bool) -> int:
        """One forced-mixed step (disaggregated prefill wave composed
        empty): temporarily drop to mixed composition so running work
        drains."""
        mode, self.mode = self.mode, "mixed"
        try:
            return self.step(_retry=_retry)
        finally:
            self.mode = mode


def generate(engine, prompts: Sequence[Sequence[int]], max_new_tokens: int = 32,
             temperature: float = 0.0, token_budget: Optional[int] = None) -> List[List[int]]:
    """Batch generation convenience over the continuous-batching loop."""
    sched = ContinuousBatchingScheduler(engine, token_budget=token_budget)
    reqs = [sched.submit(p, max_new_tokens=max_new_tokens, temperature=temperature)
            for p in prompts]
    while sched.has_work:
        if sched.step() == 0:
            break
    return [r.generated for r in reqs]
