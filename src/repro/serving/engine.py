"""Batched serving engine: continuous batching over a static slot pool,
with a paged KV-cache runtime behind it.

Requests join a pluggable :mod:`scheduler <repro.serving.scheduler>`;
free cache slots are assigned per step in ascending order (deterministic
traces, static shapes — TPU-friendly).  Admission runs *chunked batched
prefill*: one jitted ``prefill_step`` call consumes a whole chunk of
prompt tokens for every newly admitted slot at once, so a length-L
prompt costs O(L / chunk) compiled calls instead of the O(L) one-token
decodes of the legacy path (kept as a fallback for MLA models, or
``prefill_chunk=0``).  All active slots then advance one token per
``decode`` call at their *own* position.

Cache layouts (``ServingPolicy.cache``):

* ``"dense"`` — every slot statically reserves ``max_seq`` positions
  per layer (the compatibility path).
* ``"paged"`` — global-attention layers share a fixed pool of
  fixed-size blocks mapped through per-slot block tables
  (:class:`~repro.serving.kv_cache.PagedKVCache`); block allocation is
  delegated to the ``core/memory/manager.py`` allocator policies.  When
  the pool runs dry, the scheduler picks a victim to evict — its blocks
  are freed and the request is requeued (recomputed on re-admission).

The engine reads its scoped configuration from the unified runtime
Session (kernel overrides, and ``Session.serving`` for the default
``ServingPolicy``); the session is snapshotted at construction so
``engine.session.describe()`` records the serving scenario's provenance.

Models whose layers carry SSM recurrent state (mamba/jamba families)
are rejected at construction: staggered per-slot admission advances the
shared recurrence at the wrong times and silently corrupts every other
in-flight sequence — they need batch-level bulk prefill, which this
slot-granular engine does not do.
"""

from __future__ import annotations

import warnings
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.models.attention import paged_decode_path
from repro.runtime import ServingPolicy, current_session
from repro.runtime import stack as _rt

from .kv_cache import OutOfMemory, PagedKVCache
from .scheduler import make_scheduler


@dataclass
class Request:
    uid: int
    prompt: list[int]
    max_new_tokens: int = 16
    eos_id: int | None = None
    priority: int = 0                 # higher = more important (scheduler)
    deadline: float | None = None     # smaller = more urgent (scheduler)
    generated: list[int] = field(default_factory=list)
    done: bool = False
    # engine-maintained bookkeeping (monotonic repro.obs.now timestamps)
    submit_time: float = 0.0
    first_token_time: float | None = None
    token_times: list[float] = field(default_factory=list)
    admit_seq: int = -1               # admission order (victim selection)
    preemptions: int = 0


class ServeEngine:
    def __init__(self, model, params, *, batch_slots: int, max_seq: int,
                 policy: ServingPolicy | None = None, attend_fn=None,
                 draft_model=None, draft_params=None, proposer=None):
        self.model = model
        self.params = params
        self.slots = batch_slots
        self.max_seq = max_seq
        self.session = current_session()
        self.policy = policy if policy is not None else self.session.serving
        if self.policy is not self.session.serving:
            # describe() must record the scenario that actually runs
            self.session = self.session.replace(serving=self.policy)
        if attend_fn is not None:
            warnings.warn(
                "ServeEngine(attend_fn=...) is deprecated; construct the "
                "engine inside repro.session(kernels={'decode_attention': "
                "fn}) instead", DeprecationWarning, stacklevel=2)
        self.attend_fn = attend_fn or self.session.kernels.decode_attention

        # SSM-family caches are recurrent state, not position-addressed:
        # a prefill loop advances EVERY slot's recurrence, so staggered
        # (mid-flight) admission silently corrupts other sequences, and a
        # recycled slot inherits its previous occupant's state.  Allow
        # only the safe case (one pristine-slot admission into an
        # otherwise-idle engine) and raise loudly on the rest.
        self._recurrent = getattr(model, "has_recurrent_state",
                                  lambda: False)()
        self._slots_used: set[int] = set()

        self.paged = self.policy.cache == "paged"
        if self.policy.cache not in ("dense", "paged"):
            raise ValueError(f"unknown cache layout {self.policy.cache!r}")
        if self.paged:
            if not getattr(model, "supports_paged_cache", lambda: False)():
                raise ValueError(
                    "this model does not support the paged KV cache "
                    "(MLA latent caches are dense-only for now); use "
                    "ServingPolicy(cache='dense')")
            # prefix sharing needs chunked prefill (the skip is
            # chunk-aligned) and a model with no window layers; anything
            # else silently degrades to private blocks (shared_len=0)
            # so the policy stays safe to enable globally.
            self.prefix_on = (
                self.policy.prefix.enabled
                and self.policy.prefill_chunk > 0
                and getattr(model, "supports_prefix_sharing",
                            lambda: False)())
            self.kv = PagedKVCache(model, slots=batch_slots, max_seq=max_seq,
                                   block_size=self.policy.block_size,
                                   num_blocks=self.policy.num_blocks,
                                   manager=self.policy.allocator,
                                   prefix=(self.policy.prefix
                                           if self.prefix_on else None))
            self.cache = self.kv.pools
        else:
            self.prefix_on = False
            self.kv = None
            self.cache = model.init_cache(batch_slots, max_seq)

        self._chunked = (self.policy.prefill_chunk > 0 and getattr(
            model, "supports_chunked_prefill", lambda: False)())
        # speculative decoding needs the paged cache (rollback is block-
        # table truncation) on a model whose layers are all position-
        # addressed (no ring buffers); anything else silently degrades
        # to plain one-token decode so the policy is safe globally.
        spec = self.policy.speculative
        self.spec_on = (spec.enabled and self.paged
                        and getattr(model, "supports_speculative",
                                    lambda: False)())
        self.proposer = None
        if self.spec_on:
            from .speculative import make_proposer
            self.proposer = (proposer if proposer is not None else
                             make_proposer(spec, slots=batch_slots,
                                           max_seq=max_seq,
                                           draft_model=draft_model,
                                           draft_params=draft_params))
        self.scheduler = make_scheduler(self.policy.scheduler)
        # how decode attention reads the cache: each slot's live blocks in
        # place ("paged_kernel"), or a logical view ("gather": gathered
        # from the pool, or the dense cache itself)
        self.kv_path = "gather"
        if self.paged:
            cfg = model.cfg
            if any(cfg.layer_kind(i) == "A" and paged_decode_path(
                    cfg, self.kv.block_size, self.attend_fn,
                    cfg.window_for_layer(i)) == "paged_kernel"
                   for i in range(cfg.n_layers)):
                self.kv_path = "paged_kernel"
        self._decode = jax.jit(self._decode_fn)
        self._prefill = jax.jit(self._prefill_fn) if self._chunked else None
        self._verify = jax.jit(self._verify_fn) if self.spec_on else None
        self._decode_logits = jax.jit(self._decode_logits_fn)
        self.active: dict[int, Request] = {}     # slot -> request
        self.slot_pos = np.zeros(batch_slots, np.int32)
        self.slot_tok = np.zeros((batch_slots, 1), np.int32)
        self.steps = 0
        self.decode_calls = 0
        self.prefill_calls = 0
        self.preemptions = 0
        self.prefill_tokens_saved = 0
        self.shared_admissions = 0
        self._admit_counter = 0
        # speculative / beam bookkeeping
        self.spec_rounds = 0
        self.slot_rounds = 0     # (slot, round) verify instances
        self.verify_calls = 0
        self.accepted_tokens = 0
        self.rejected_tokens = 0
        self.fork_counts: dict[int, int] = {}    # slot -> forks taken
        # observability: the pinned session's tracer, or None (the off
        # path is this one attribute check per site)
        self._obs = obs.get_tracer(self.session)
        if self._obs is not None:
            m = self._obs.metrics
            self._h_ttft = m.histogram("serving.ttft_s")
            self._h_itl = m.histogram("serving.inter_token_s")
            self._g_free = m.gauge("kv.free_blocks")
            self._g_cow = m.gauge("kv.cow_copies")
            self._g_prefix = m.gauge("kv.prefix_hits")
            self._gauge_vals: tuple | None = None

    # -- jitted bodies -------------------------------------------------------
    def _decode_fn(self, params, cache, tok, pos, block_table):
        # pin the construction-time session during tracing: whatever is
        # ambient when jit first traces must not leak into the compiled
        # decode (describe() provenance has to match actual behavior)
        with _rt.session(self.session):
            if block_table is None:
                logits, cache = self.model.decode_step(
                    params, cache, tok, pos, attend_fn=self.attend_fn)
            else:
                logits, cache = self.model.decode_step(
                    params, cache, tok, pos, attend_fn=self.attend_fn,
                    block_table=block_table)
        next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_tok[:, None], cache

    def _prefill_fn(self, params, cache, toks, start, count, block_table):
        with _rt.session(self.session):
            return self.model.prefill_step(params, cache, toks, start,
                                           count, block_table=block_table)

    def _verify_fn(self, params, cache, toks, start, count, block_table):
        # wide verify: per-slot [start, start+count) token spans written
        # through the chunked-prefill path, greedy targets for every
        # position argmaxed on device
        with _rt.session(self.session):
            logits, cache = self.model.verify_step(
                params, cache, toks, start, count, block_table=block_table)
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return greedy, cache

    def _decode_logits_fn(self, params, cache, tok, pos, block_table):
        # beam-search body: like _decode_fn but returns full next-token
        # log-probs so the caller can expand/score hypotheses
        with _rt.session(self.session):
            if block_table is None:
                logits, cache = self.model.decode_step(
                    params, cache, tok, pos, attend_fn=self.attend_fn)
            else:
                logits, cache = self.model.decode_step(
                    params, cache, tok, pos, attend_fn=self.attend_fn,
                    block_table=block_table)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        return logp, cache

    def _block_table(self):
        return self.kv.device_table() if self.paged else None

    def _span(self, name: str, **attrs):
        """A span on the pinned session's tracer and, while a profiler
        session collects, on the profiler's trace (``repro.obs.span``)."""
        return obs.span(name, "serving", self._obs, **attrs)

    # -- request lifecycle ---------------------------------------------------
    def submit(self, req: Request) -> None:
        req.submit_time = obs.now()
        if self._obs is not None:
            self._obs.instant("request.submit", "serving",
                              ts=req.submit_time, uid=req.uid,
                              prompt_tokens=len(req.prompt))
        self.scheduler.submit(req)

    @property
    def waiting(self) -> int:
        """Requests queued in the scheduler (not yet admitted)."""
        return len(self.scheduler)

    def _admit(self) -> list[tuple[int, Request, list[int], int]]:
        """Pop waiting requests into the free slots, lowest first
        (deterministic traces), until the queue or the slots run out or
        the pool is dry; then prefill the admitted ones together."""
        free = sorted(s for s in range(self.slots) if s not in self.active)
        admitted: list[tuple[int, Request, list[int], int]] = []
        with self._span("serve.admit") as sp:
            while free:
                req = self.scheduler.pop()
                if req is None:
                    break
                entry = self._admit_request(req, free.pop(0))
                if entry is None:
                    break
                admitted.append(entry)
            if sp is not None:
                sp.attrs["admitted"] = len(admitted)
        if admitted:
            if self._chunked:
                self._prefill_chunked(admitted)
            else:
                for slot, _req, eff, _shared in admitted:
                    self._prefill_per_token(slot, eff)
        return admitted

    def _admit_request(self, req: Request, slot: int
                       ) -> tuple[int, Request, list[int], int] | None:
        """Map ``req``'s KV into ``slot``: the longest cached prefix, the
        private tail, copy-on-write of a shared block the prefill will
        diverge into, and registration of its full blocks.  Returns
        ``(slot, req, effective prompt, shared positions)``, or ``None``
        when the pool is dry and the request went back to the queue."""
        if self._recurrent and (self.active or slot in self._slots_used):
            raise ValueError(
                "SSM-family models carry recurrent state: admitting "
                "request %d %s would advance the shared recurrence at "
                "the wrong times and silently corrupt decoding; SSM "
                "serving supports one request per pristine slot at a "
                "time (use batch-level bulk prefill — model.prefill — "
                "for concurrent SSM workloads)" % (
                    req.uid, "mid-flight" if self.active
                    else f"into recycled slot {slot}"))
        self._slots_used.add(slot)
        # a preempted request resumes from prompt + tokens so far
        eff = req.prompt + req.generated
        if len(eff) - 1 >= self.max_seq:
            raise ValueError(
                f"request {req.uid} prompt ({len(eff)} tokens) does "
                f"not fit max_seq={self.max_seq}; requeueing would "
                "spin forever")
        shared = 0
        with self._span("serve.admit_request", uid=req.uid,
                        prompt_tokens=len(eff)) as sp:
            if self.paged:
                if self.kv.blocks_for(len(eff) - 1) > self.kv.usable_blocks:
                    raise OutOfMemory(
                        f"request {req.uid} needs more KV blocks than the "
                        f"whole pool holds ({self.kv.usable_blocks} usable "
                        f"blocks of {self.kv.block_size} positions)")
                try:
                    if self.prefix_on:
                        # map the longest cached prefix, then grow the
                        # private tail behind it
                        shared = self.kv.admit(slot, eff)
                    self.kv.ensure(slot, len(eff) - 1)
                    if self.prefix_on:
                        n = len(eff) - 1
                        if shared < n:
                            # the prefill round will write [c0, n); COW
                            # any still-shared block it diverges into
                            # *before* the tokens land
                            t = self.policy.prefill_chunk
                            c0 = (shared // t) * t
                            self.cache = self.kv.prepare_write(
                                slot, c0, n - 1, self.cache)
                        # publish this prompt's full blocks for later
                        # admissions (ready after the prefill round)
                        self.kv.register(slot, eff[:n])
                except OutOfMemory:
                    # pool dry: roll back any partial allocation and wait
                    # for active slots to finish (or get evicted later)
                    self.kv.release(slot)
                    self._audit_kv()
                    if self._obs is not None:
                        self._obs.instant("request.requeue", "serving",
                                          uid=req.uid, reason="admit-oom")
                    self.scheduler.requeue(req)
                    return None
                if shared:
                    self.shared_admissions += 1
            if sp is not None:
                sp.attrs["shared"] = shared
                sp.attrs["queued_ms"] = (obs.now() - req.submit_time) * 1e3
        req.admit_seq = self._admit_counter
        self._admit_counter += 1
        self.active[slot] = req
        self.slot_pos[slot] = len(eff) - 1
        self.slot_tok[slot, 0] = eff[-1]
        if self._obs is not None:
            self._obs.instant("request.admit", "serving", uid=req.uid,
                              slot=slot, admit_seq=req.admit_seq,
                              prompt_tokens=len(eff), shared=shared)
        return slot, req, eff, shared

    def _prefill_chunked(self, admitted) -> None:
        """All newly admitted slots prefill together, one jitted call per
        chunk: ceil(max_prompt_len / chunk) calls per admission round.

        With prefix sharing, a slot whose leading ``shared`` positions
        came out of the radix tree starts at the chunk boundary below
        the match (``c0``) — recomputing the partial-chunk tail [c0,
        shared) keeps the chunk grid, and therefore the numerics,
        bit-identical to the sharing-off path (the rewrites are
        idempotent: identical values at identical positions).  A slot
        whose whole prompt is cached skips prefill entirely.

        ``serve.prefill_chunk`` spans time one call's host dispatch; the
        call's device time is the ``_prefill_fn`` program on the
        profiler's trace.
        """
        t = self.policy.prefill_chunk
        with self._span("serve.prefill") as sp:
            plan = []                        # (slot, eff, c0)
            saved = 0
            for slot, _req, eff, shared in admitted:
                n = len(eff) - 1
                if self.prefix_on and shared >= n:
                    saved += n
                    continue
                c0 = (min(shared, n) // t) * t if self.prefix_on else 0
                saved += c0
                plan.append((slot, eff, c0))
            self.prefill_tokens_saved += saved
            calls = needed = 0
            if plan:
                longest = max(len(eff) - 1 for _s, eff, _c in plan)
                first = min(c0 for _s, _e, c0 in plan)
                bt = self._block_table()
                for c in range(first, longest, t):
                    toks = np.zeros((self.slots, t), np.int32)
                    start = np.zeros(self.slots, np.int32)
                    count = np.zeros(self.slots, np.int32)
                    real = 0                 # prompt tokens in this call
                    for slot, eff, c0 in plan:
                        if c < c0:
                            continue
                        seg = eff[:-1][c:c + t]
                        if not seg:
                            continue
                        toks[slot, :len(seg)] = seg
                        start[slot] = c
                        count[slot] = len(seg)
                        real += len(seg)
                    with self._span("serve.prefill_chunk", tokens=real,
                                    capacity=self.slots * t):
                        self.cache = self._prefill(self.params, self.cache,
                                                   jnp.asarray(toks),
                                                   jnp.asarray(start),
                                                   jnp.asarray(count), bt)
                    self.prefill_calls += 1
                    calls += 1
                    needed += real
            if sp is not None:
                sp.attrs.update(calls=calls, tokens_needed=needed,
                                tokens_saved=saved)
        if self.prefix_on:
            # device content for this round's registrations now exists
            for slot, _req, _eff, _shared in admitted:
                self.kv.mark_ready(slot)

    def _prefill_per_token(self, slot: int, eff: list[int]) -> None:
        # Legacy fallback (MLA / prefill_chunk=0): feed prompt tokens
        # through decode steps.  Other slots are fed their own current
        # (token, position), so their cache writes land where the next
        # decode step would write the identical values — idempotent for
        # position-addressed attention caches.
        bt = self._block_table()
        with self._span("serve.prefill_legacy", slot=slot,
                        tokens=len(eff) - 1):
            for i, tok in enumerate(eff[:-1]):
                tkn = self.slot_tok.copy()
                tkn[slot, 0] = tok
                pos = self.slot_pos.copy()
                pos[slot] = i
                _, self.cache = self._decode(self.params, self.cache,
                                             jnp.asarray(tkn),
                                             jnp.asarray(pos), bt)
                self.prefill_calls += 1

    # -- static audit --------------------------------------------------------
    def _audit_kv(self) -> None:
        """Audit the paged block tables after a release when the pinned
        session's :class:`~repro.runtime.AnalysisPolicy` asks for it
        (``audit_serving=True``, or always at ``"strict"``).  A leak,
        double-free, or trash-block violation raises
        :class:`~repro.analysis.AnalysisError` at the release that caused
        it instead of surfacing as cross-request corruption later."""
        pol = self.session.analysis
        if not pol.enabled or not (pol.strict or pol.audit_serving):
            return
        report = self.kv.audit()
        report.raise_if_errors(context="paged KV cache audit")

    # -- preemption ----------------------------------------------------------
    def _preempt(self, slot: int) -> None:
        req = self.active.pop(slot)
        self.slot_pos[slot] = 0
        req.preemptions += 1
        self.preemptions += 1
        if self._obs is not None:
            self._obs.instant("request.preempt", "serving", uid=req.uid,
                              slot=slot, generated=len(req.generated))
        self.kv.release(slot)
        if self.spec_on:
            self.proposer.release(slot)
        self._audit_kv()
        if self._obs is not None:
            self._obs.instant("request.requeue", "serving", uid=req.uid,
                              reason="preempt")
        self.scheduler.requeue(req)

    def _ensure_capacity(self) -> None:
        """Paged mode: every active slot must be able to write its next
        position; when the pool runs dry, evict scheduler-chosen victims
        (their blocks free, the requests requeue and recompute later)."""
        for slot in sorted(self.active):
            while slot in self.active:
                try:
                    p = int(self.slot_pos[slot])
                    self.kv.ensure(slot, p)
                    if self.prefix_on:
                        # decode is about to write position p: give the
                        # slot a private copy of a still-shared block
                        # before the first divergent token lands
                        self.cache = self.kv.prepare_write(
                            slot, p, p, self.cache)
                    break
                except OutOfMemory:
                    others = {s: r for s, r in self.active.items()
                              if s != slot}
                    if not others:
                        # this request alone exhausts the pool
                        self._preempt(slot)
                        raise
                    self._preempt(self.scheduler.choose_victim(others))

    # -- stepping ---------------------------------------------------------------
    def step(self) -> list[Request]:
        """Advance all active slots; returns finished requests.

        Plain mode emits one token per slot per step; speculative mode
        runs one draft-propose / wide-verify round emitting 1..k+1
        tokens per slot (token-for-token identical output)."""
        with self._span("serve.step", step=self.steps,
                        active=len(self.active), waiting=self.waiting):
            return self._spec_step() if self.spec_on else self._plain_step()

    def _plain_step(self) -> list[Request]:
        self._admit()
        if not self.active:
            return []
        if self.paged:
            with self._span("serve.ensure_capacity"):
                self._ensure_capacity()
            if not self.active:
                return []
        # the span covers dispatch AND the host sync (np.asarray), so its
        # duration is the real step latency, not just dispatch time
        with self._span("serve.decode_step", active=len(self.active),
                        kv_path=self.kv_path) as sp:
            if sp is not None:
                live = self.slot_pos[list(self.active)]
                sp.attrs["keys"] = len(self.active) + int(live.sum())
                if self.paged:
                    # blocks the decode attends over (the kernel's reads)
                    sp.attrs["kv_blocks"] = int(
                        (live // self.kv.block_size + 1).sum())
            with self._span("serve.decode_dispatch"):
                tok = jnp.asarray(self.slot_tok)
                pos = jnp.asarray(self.slot_pos)         # per-slot positions
                next_tok, self.cache = self._decode(
                    self.params, self.cache, tok, pos, self._block_table())
            self.decode_calls += 1
            with self._span("serve.decode_wait"):
                next_np = np.asarray(next_tok)
        now = obs.now()
        finished = []
        with self._span("serve.emit") as sp:
            for slot, req in list(self.active.items()):
                t = int(next_np[slot, 0])
                self._emit_token(req, t, now)
                self.slot_tok[slot, 0] = t
                self.slot_pos[slot] += 1
                if ((req.eos_id is not None and t == req.eos_id)
                        or len(req.generated) >= req.max_new_tokens
                        or self.slot_pos[slot] >= self.max_seq - 1):
                    req.done = True
                    finished.append(req)
                    del self.active[slot]
                    # an idle slot decodes position 0 into the trash block
                    self.slot_pos[slot] = 0
                    if self._obs is not None:
                        self._obs.instant("request.done", "serving", ts=now,
                                          uid=req.uid,
                                          tokens=len(req.generated))
                    if self.paged:
                        self.kv.release(slot)
                        self._audit_kv()
            self._sample_gauges()
            if sp is not None:
                sp.attrs["finished"] = len(finished)
        self.steps += 1
        return finished

    def _emit_token(self, req: Request, t: int, now: float) -> None:
        """Record one emitted token: benchmark-side fields (generated /
        token_times / first_token_time) and — when observability is on —
        the trace instants and latency histograms, all stamped with the
        SAME clock sample so trace summaries and benchmark math agree."""
        req.generated.append(t)
        req.token_times.append(now)
        first = req.first_token_time is None
        if first:
            req.first_token_time = now
        if self._obs is not None:
            self._obs.instant("request.token", "serving", ts=now,
                              uid=req.uid, token=t)
            if first:
                self._obs.instant("request.first_token", "serving", ts=now,
                                  uid=req.uid)
                self._h_ttft.observe(now - req.submit_time)
            else:
                self._h_itl.observe(now - req.token_times[-2])

    def _sample_gauges(self) -> None:
        if self._obs is None or not self.paged:
            return
        # gauges also append a counter-track sample per set(); skip
        # unchanged values so steady-state steps stay cheap
        vals = (self.kv.usable_blocks - self.kv.blocks_in_use,
                self.kv.cow_copies,
                getattr(getattr(self.kv, "prefix_index", None), "hits", 0))
        if vals == self._gauge_vals:
            return
        self._gauge_vals = vals
        self._g_free.set(vals[0])
        self._g_cow.set(vals[1])
        if getattr(self.kv, "prefix_index", None) is not None:
            self._g_prefix.set(vals[2])

    def _spec_step(self) -> list[Request]:
        """One draft-propose / wide-verify / rollback round.

        Per active slot with last emitted token ``t`` at position ``p``
        and proposals ``d_1..d_{c-1}``: the verify call feeds
        ``[t, d_1..d_{c-1}]`` at positions ``p..p+c-1`` (one batched
        forward, per-slot width via count masks) and argmaxes greedy
        targets ``g_0..g_{c-1}``.  The accepted prefix is the longest
        ``a`` with ``d_{i+1} == g_i``; the slot emits ``d_1..d_a, g_a``
        — every emitted token equals what sequential greedy decode
        would have produced, which is the identity guarantee.  KV for
        the rejected suffix rolls back by block-table truncation.
        """
        admitted = self._admit()
        for slot, _req, eff, _shared in admitted:
            n = len(eff) - 1
            # prefill wrote positions [0, n); the audit treats anything
            # held beyond that without a declared write intent as
            # rollback debris, so record both
            self.kv.set_committed(slot, n)
            if slot not in self.kv._prepared:
                self.kv.begin_write(slot, max(n - 1, 0), max(n - 1, 0))
            self.proposer.admit(slot, eff)
        if not self.active:
            return []
        k = self.policy.speculative.k
        width = k + 1
        contexts = {s: r.prompt + r.generated
                    for s, r in self.active.items()}
        with self._span("serve.spec_propose", slots=len(contexts), k=k):
            proposals = self.proposer.propose(contexts, k)
        counts: dict[int, tuple[int, list[int]]] = {}
        for s in list(self.active):
            props = [int(t) for t in proposals.get(s, [])][:k]
            # verify writes positions p..p+c-1; clamp inside the cache
            c = min(len(props) + 1, width,
                    self.max_seq - int(self.slot_pos[s]))
            counts[s] = (c, props[:c - 1])
        # grow + COW ahead of the wide write; the write intent is
        # declared *before* ensure so a mid-growth preemption audit
        # sees intended blocks, not dangling ones
        for slot in sorted(self.active):
            while slot in self.active:
                p = int(self.slot_pos[slot])
                hi = p + counts[slot][0] - 1
                try:
                    self.kv.begin_write(slot, p, hi)
                    self.kv.ensure(slot, hi)
                    self.cache = self.kv.prepare_write(slot, p, hi,
                                                       self.cache)
                    break
                except OutOfMemory:
                    others = {s: r for s, r in self.active.items()
                              if s != slot}
                    if not others:
                        self._preempt(slot)
                        raise
                    self._preempt(self.scheduler.choose_victim(others))
        if not self.active:
            return []
        toks = np.zeros((self.slots, width), np.int32)
        start = np.zeros(self.slots, np.int32)
        count = np.zeros(self.slots, np.int32)
        for s in self.active:
            c, props = counts[s]
            span = [int(self.slot_tok[s, 0])] + props
            toks[s, :c] = span[:c]
            start[s] = self.slot_pos[s]
            count[s] = c
        with self._span("serve.spec_verify", slots=len(self.active),
                        width=width):
            greedy, self.cache = self._verify(self.params, self.cache,
                                              jnp.asarray(toks),
                                              jnp.asarray(start),
                                              jnp.asarray(count),
                                              self._block_table())
            self.verify_calls += 1
            g = np.asarray(greedy)
        self.spec_rounds += 1
        self.slot_rounds += len(self.active)
        now = obs.now()
        finished = []
        accepted_map: dict[int, int] = {}
        for slot, req in list(self.active.items()):
            _c, props = counts[slot]
            a = 0
            while a < len(props) and props[a] == int(g[slot, a]):
                a += 1
            emit = props[:a] + [int(g[slot, a])]
            accepted_map[slot] = a
            self.accepted_tokens += a
            self.rejected_tokens += len(props) - a
            if self._obs is not None:
                self._obs.instant("spec.round", "serving", ts=now,
                                  uid=req.uid, slot=slot, accepted=a,
                                  rejected=len(props) - a)
            p0 = int(self.slot_pos[slot])
            done = False
            n_emit = 0
            for t in emit:
                self._emit_token(req, t, now)
                n_emit += 1
                if ((req.eos_id is not None and t == req.eos_id)
                        or len(req.generated) >= req.max_new_tokens
                        or p0 + n_emit >= self.max_seq - 1):
                    done = True
                    break
            new_pos = p0 + n_emit
            self.slot_pos[slot] = new_pos
            self.slot_tok[slot, 0] = emit[n_emit - 1]
            # truncate the rejected suffix: KV past new_pos-1 is
            # either unwritten (the bonus token) or rejected content
            freed = self.kv.rollback(slot, new_pos)
            if self._obs is not None and len(props) - a:
                self._obs.instant("kv.rollback", "serving", ts=now,
                                  uid=req.uid, slot=slot, pos=new_pos,
                                  blocks_freed=freed)
            if done:
                req.done = True
                finished.append(req)
                del self.active[slot]
                if self._obs is not None:
                    self._obs.instant("request.done", "serving", ts=now,
                                      uid=req.uid,
                                      tokens=len(req.generated))
                self.kv.release(slot)
                self.proposer.release(slot)
                self._audit_kv()
        self.proposer.commit(accepted_map)
        self._sample_gauges()
        self.steps += 1
        return finished

    # -- beam forking --------------------------------------------------------
    def fork(self, src: int, dst: int) -> None:
        """Clone slot ``src``'s sequence state into free slot ``dst``:
        block table refcount++ per block, copy-on-write on the first
        divergent write (see ``serving/beam.py`` for the consumer)."""
        if not self.paged:
            raise ValueError("fork() requires the paged KV cache")
        self.kv.fork(src, dst)
        self.slot_pos[dst] = self.slot_pos[src]
        self.slot_tok[dst] = self.slot_tok[src]
        self.fork_counts[src] = self.fork_counts.get(src, 0) + 1
        if self._obs is not None:
            self._obs.instant("kv.fork", "serving", src=src, dst=dst)

    def run_until_done(self, max_steps: int = 10000) -> list[Request]:
        out = []
        for _ in range(max_steps):
            out.extend(self.step())
            if not self.active and not len(self.scheduler):
                break
        return out

    # -- provenance ----------------------------------------------------------
    def describe(self) -> dict:
        """Serving-scenario snapshot for logs and benchmark provenance."""
        d = {"session": self.session.describe(),
             "slots": self.slots, "max_seq": self.max_seq,
             "chunked_prefill": self._chunked,
             "decode_kv_path": self.kv_path,
             "decode_calls": self.decode_calls,
             "prefill_calls": self.prefill_calls,
             "preemptions": self.preemptions,
             "prefix_sharing": self.prefix_on,
             "prefill_tokens_saved": self.prefill_tokens_saved,
             "shared_admissions": self.shared_admissions}
        spec = {"enabled": self.spec_on,
                "rounds": self.spec_rounds,
                "slot_rounds": self.slot_rounds,
                "verify_calls": self.verify_calls,
                "accepted_tokens": self.accepted_tokens,
                "rejected_tokens": self.rejected_tokens,
                # mean tokens a slot emits per verify round, in
                # [1, k + 1] (one-token decode is exactly 1.0) — the
                # speculative speedup knob
                "accepted_per_step": round(
                    (self.accepted_tokens + self.slot_rounds)
                    / max(1, self.slot_rounds), 3)}
        if self.proposer is not None:
            spec["proposer"] = self.proposer.describe()
        d["speculative"] = spec
        d["fork_counts"] = dict(self.fork_counts)
        if self.paged:
            d["kv_cache"] = self.kv.describe()
        return d
