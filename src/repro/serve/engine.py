"""Serving engines: static-batch baseline + batch-invariant continuous batching.

``Engine`` is the original static-batch greedy/sampled loop (kept as the
benchmark baseline; its outputs depend on batch composition because rows share
one padded shape and one sampling key per step).  ``ContinuousEngine`` is the
deterministic serving engine this module is really about:

  * **paged KV** (:mod:`repro.serve.kv_cache`) — per-request page tables over a
    fixed pool; physical placement is irrelevant to the math;
  * **deterministic scheduling** (:mod:`repro.serve.scheduler`) — FCFS by
    request id, lowest free slot/page first: the schedule is a pure function of
    the request stream;
  * **chunked prefill** — prompts are processed per-request in fixed-size
    chunks (B=1, L=chunk jit shape), so a request's prefill compute never
    depends on what else is in flight;
  * **in-flight batched decode** — one token per active slot per step over a
    fixed (n_slots, 1) shape; idle rows carry garbage that is never read;
  * **per-request sampling keys** — ``fold_in(fold_in(key(seed), request_id),
    token_index)``, vmapped per row, so sampling is independent of slot
    placement and co-batch.

Contract (README §Serving, enforced by tests/test_serve_invariance.py): for a
fixed (params, prompt tokens, seed, sampling config), a request's emitted
tokens are bitwise identical across co-batch composition, batch size, prompt
padding, arrival order, and prefill chunk size — and, with the optional
``mesh`` argument (TP over a ``"model"`` axis, :mod:`repro.serve.sharded`),
across tensor-parallel degrees and mesh shapes too: every row-parallel
reduction takes the canonical virtual-shard fold form
(:mod:`repro.dist.fold`), so TP=1/2/4 compute the same fold tree bitwise.

The contract also survives faults (README §Robustness, proven by
tests/test_chaos_conformance.py): with ``faults=`` an armed
:class:`repro.faults.Injector`, the engine absorbs KV-pool exhaustion, slot
revocation and decode stalls by **deterministic preemption** — the victim is
always the active request with the highest id; its pages are freed and it is
later restored by chunked-prefill *recompute* of its full generated prefix,
so the continuation is bitwise identical to never having been preempted
(already-sampled tokens are kept, never re-drawn).  ``max_queue_depth``
bounds admission with load shedding decided purely by (request id, queue
state); ``deadline_steps`` cancels in *engine steps*, never wall clock; and
``snapshot_dir``/``snapshot_every`` persist the full engine state through the
manifest-v2 digest machinery so a crashed engine resumes every in-flight
stream bitwise (:mod:`repro.serve.snapshot`).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.dist import fold
from repro.models import transformer as T
from repro.serve.kv_cache import PagedKVCache, PagedLayout
from repro.serve.scheduler import FCFSScheduler, Request


class QueueFull(RuntimeError):
    """Deterministic load shedding: the bounded queue rejected a request.

    The rejection is a pure function of (request id, queue state) — never of
    arrival timing — so the same request stream is shed identically on every
    run.  Carries ``(req_id, depth)``; the engine also records the rejection
    in :attr:`ContinuousEngine.rejected`.
    """

    def __init__(self, req_id: int, depth: int):
        self.req_id, self.depth = req_id, depth
        super().__init__(
            f"request {req_id} shed: queue depth is at the "
            f"max_queue_depth={depth} bound")


@dataclasses.dataclass(frozen=True)
class SampleConfig:
    """Pinned sampling semantics (README §Serving).

    ``temperature == 0`` is greedy: argmax over the raw logits, and the
    reported logprob is ``log_softmax(raw logits)[tok]`` — the *raw-softmax*
    probability, untouched by ``top_k`` (there is no truncated distribution
    to report under greedy).  ``temperature > 0`` samples from the
    transformed distribution (temperature then top-k) and reports
    ``log_softmax(transformed logits)[tok]``.  ``top_k`` keeps **exactly k**
    tokens: ties at the k-th logit break deterministically toward the lowest
    token id (see :func:`_transform_logits`)."""
    temperature: float = 0.0      # 0 = greedy
    top_k: int = 0                # 0 = no truncation
    seed: int = 0
    eos_id: Optional[int] = None


def _transform_logits(logits, scfg: SampleConfig):
    """Temperature/top-k transform over the last (vocab) axis — shared by the
    static batched sampler and the continuous per-row sampler so the two
    engines always sample from the same distribution for one SampleConfig.

    top-k keeps **exactly k** tokens.  A threshold test (``logits < kth``)
    would keep every token tied at the k-th value — the support would then
    depend on how many ties the layout happens to have, violating the
    pinned-distribution contract speculative verification relies on.  The
    keep-set is instead the index set ``lax.top_k`` returns, which breaks
    ties deterministically toward the **lowest token id**."""
    logits = logits / scfg.temperature
    if scfg.top_k:
        _, idx = jax.lax.top_k(logits, scfg.top_k)
        iota = jnp.arange(logits.shape[-1], dtype=idx.dtype)
        keep = jnp.any(idx[..., :, None] == iota, axis=-2)
        logits = jnp.where(keep, logits, -1e30)
    return logits


def _sample_rows(logits, req_ids, steps, scfg: SampleConfig):
    """Keyed per-row sampler core: ``(B, V) logits -> (tokens (B,), logprobs
    (B,))`` with key ``fold_in(fold_in(key(seed), request_id), token_index)``
    per row.  This is *the* sampling rule of the continuous engine — the
    standalone jitted sampler (:func:`_sampler_fn`) and the in-scan sampler of
    the speculative round (:mod:`repro.serve.spec`) both trace exactly this
    function, so speculative acceptance ("draft == the keyed sample") compares
    like with like.

    Logprob contract (pinned; asserted in tests/test_serve_invariance.py):
    greedy reports ``log_softmax(raw logits)[tok]``; sampled reports
    ``log_softmax(transformed logits)[tok]``."""
    logits = logits.astype(jnp.float32)
    if scfg.temperature == 0.0:
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        lp = jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1),
                                 tok[:, None], axis=-1)[:, 0]
        return tok, lp
    base = jax.random.PRNGKey(scfg.seed)

    def one(row, rid, t):
        k = jax.random.fold_in(jax.random.fold_in(base, rid), t)
        tl = _transform_logits(row, scfg)
        tok = jax.random.categorical(k, tl).astype(jnp.int32)
        return tok, jax.nn.log_softmax(tl)[tok]

    return jax.vmap(one)(logits, req_ids, steps)


def _sample(logits, scfg: SampleConfig, step_key):
    """logits: (B, 1, V) → tokens (B, 1). Deterministic given step_key."""
    logits = logits[:, 0].astype(jnp.float32)
    if scfg.temperature == 0.0:
        return jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    logits = _transform_logits(logits, scfg)
    return jax.random.categorical(step_key, logits)[:, None].astype(jnp.int32)


class Engine:
    """Static-batch engine (baseline). One padded batch in, lockstep decode."""

    def __init__(self, cfg, params, max_seq: int, scfg: SampleConfig = SampleConfig()):
        self.cfg, self.params, self.max_seq, self.scfg = cfg, params, max_seq, scfg
        self.last_decode_steps = 0        # poll-every-step reference count
        self.dispatched_decode_steps = 0  # decodes actually dispatched
        self._prefill = jax.jit(
            lambda p, b: T.prefill_step(p, b, cfg, max_seq=max_seq))
        self._decode = jax.jit(
            lambda p, c, t, pos, cx: T.decode_step(p, c, t, pos, cfg, cross_x=cx))

    def generate(self, batch, n_tokens: int):
        """batch: dict with 'tokens' (B, S_prompt) (+ frontend inputs).
        Returns (B, n_tokens) int32, deterministic for a fixed seed.

        ``last_decode_steps`` afterwards is a pure function of the emitted
        stream — the decode count a poll-every-step loop would execute — so
        it is bitwise identical whether or not the amortized all-EOS fast
        path fired; ``dispatched_decode_steps`` counts the decodes this call
        actually dispatched (≤ 7 more, up to the next poll boundary)."""
        logits, caches, cross_x = self._prefill(self.params, batch)
        key = jax.random.PRNGKey(self.scfg.seed)
        tok = _sample(logits, self.scfg, jax.random.fold_in(key, 0))
        prompt_len = batch["tokens"].shape[1]
        if self.cfg.frontend == "vision":
            prompt_len += self.cfg.frontend_len
        out = [tok]
        done = jnp.zeros((tok.shape[0], 1), bool)
        self.dispatched_decode_steps = 0
        for i in range(1, n_tokens):
            if self.scfg.eos_id is not None:
                done = done | (tok == self.scfg.eos_id)
                # all-done probe forces a device sync, so amortize it: poll
                # every 8 steps instead of serializing every dispatch on it.
                if i % 8 == 0 and bool(jnp.all(done)):
                    # all rows finished: the remaining tokens are forced to
                    # eos anyway — emit them host-side and skip the decodes,
                    # keeping tok/done consistent with the per-step loop
                    # (every remaining position is eos and every row done).
                    tail = jnp.full((tok.shape[0], n_tokens - i),
                                    self.scfg.eos_id, jnp.int32)
                    out.append(tail)
                    tok = tail[:, -1:]
                    break
            logits, caches = self._decode(self.params, caches, tok,
                                          jnp.asarray(prompt_len + i - 1), cross_x)
            self.dispatched_decode_steps += 1
            nxt = _sample(logits, self.scfg, jax.random.fold_in(key, i))
            if self.scfg.eos_id is not None:
                nxt = jnp.where(done, self.scfg.eos_id, nxt)
            out.append(nxt)
            tok = nxt
        gen = jnp.concatenate(out, axis=1)
        # stream-pure accounting: the poll-every-step loop stops decoding at
        # max over rows of the first-eos index (n_tokens-1 if a row never
        # emits eos) — recompute that from the stream instead of counting
        # dispatches, so the fast path can never skew the telemetry.
        if self.scfg.eos_id is None:
            self.last_decode_steps = n_tokens - 1
        else:
            g = np.asarray(gen)
            is_eos = g == self.scfg.eos_id
            first = np.where(is_eos.any(axis=1), is_eos.argmax(axis=1),
                             n_tokens - 1)
            self.last_decode_steps = int(first.max()) if first.size else 0
        return gen


# --------------------------------------------------------------------------- #
# continuous batching
# --------------------------------------------------------------------------- #
@functools.lru_cache(maxsize=None)
def _paged_step_fn(cfg):
    """Shared jitted paged step — cached per (hashable, frozen) config so many
    engine instances (the invariance suite builds dozens) reuse compilations.
    Compiled like the sharded step (``fold.exact_jit``), so the two round
    alike."""
    return fold.exact_jit(functools.partial(T.paged_step, cfg=cfg))


@functools.lru_cache(maxsize=None)
def _sampler_fn(scfg: SampleConfig):
    """Per-request-keyed row sampler: ``fold_in(fold_in(key(seed), request_id),
    token_index)`` vmapped per row — sampling never sees slot placement or
    co-batch, which is half of the batch-invariance contract (the other half
    is the fixed-order paged attention reduction).

    Returns ``(tokens (B,), logprobs (B,))``: the log-probability of the
    chosen token under the distribution it was drawn from (sampled reports
    the post-temperature/top-k softmax; greedy reports the **raw** softmax —
    the pinned contract on :func:`_sample_rows`) — part of the
    topology-invariance contract, so the mesh-axis tests can assert sampled
    logprobs bitwise too."""
    return jax.jit(functools.partial(_sample_rows, scfg=scfg))


@dataclasses.dataclass
class _Active:
    """Host-side per-slot decode state."""
    req: Request
    produced: List[int]
    logprobs: List[float] = dataclasses.field(default_factory=list)
    done: bool = False

    @property
    def next_pos(self) -> int:
        # position of the last sampled (not yet KV-written) token
        return len(self.req.tokens) + len(self.produced) - 1


class ContinuousEngine:
    """Continuous-batching deterministic engine over paged KV slots."""

    def __init__(self, cfg, params, *, n_slots: int = 4, max_seq: int = 128,
                 page_size: int = 16, n_pages: Optional[int] = None,
                 prefill_chunk: int = 32, scfg: SampleConfig = SampleConfig(),
                 tracker=None, mesh=None, capture_prefill_logits: bool = False,
                 faults=None, max_queue_depth: Optional[int] = None,
                 snapshot_dir: Optional[str] = None,
                 snapshot_every: Optional[int] = None,
                 spec_k: int = 0, draft_cfg=None, draft_params=None,
                 run_id: Optional[str] = None):
        """``mesh``: optional :class:`jax.sharding.Mesh` with a ``"model"``
        axis — the jitted step becomes the TP-sharded shard_map step
        (:mod:`repro.serve.sharded`); tokens/logprobs are bitwise identical
        to ``mesh=None`` for every TP degree and mesh shape (the
        topology-invariance contract, README §Serving).
        ``capture_prefill_logits``: keep each request's per-position prefill
        logits in ``self.prefill_logits[req_id]`` (train≡serve parity tests).

        Robustness knobs (README §Robustness; all default-off, and the
        default path is bitwise identical to an engine without them):
        ``faults``: an armed :class:`repro.faults.Injector` whose plan this
        engine consumes at the matching step indices; ``max_queue_depth``:
        bound on pending requests — ``submit`` beyond it raises
        :class:`QueueFull` deterministically; ``snapshot_dir`` +
        ``snapshot_every``: persist a full engine snapshot every N engine
        steps (manifest-v2 digests, :mod:`repro.serve.snapshot`) so
        :meth:`from_snapshot` can resume after a crash.

        Speculative decoding (README §Serving, :mod:`repro.serve.spec`):
        ``spec_k >= 1`` drafts ``spec_k`` tokens per live slot per engine
        step and verifies them with exact acceptance, so the committed
        tokens *and logprobs* stay bitwise identical to ``spec_k=0`` —
        speculation is a pure throughput knob, composable with every other
        contract (co-batch, mesh, chaos, snapshot).  ``draft_params`` (with
        optional ``draft_cfg``, same vocab) selects a separate drafter;
        ``None`` self-drafts with the target itself (acceptance 1.0).
        """
        assert T.supports_paged(cfg), (
            "paged serving covers decoder-only, attention-only LMs")
        assert max_seq % page_size == 0 and prefill_chunk >= 1
        self.cfg, self.params, self.scfg = cfg, params, scfg
        # observation only: every tracker call logs host-side ints already
        # computed for the step — swapping the tracker can never change a
        # token (tests/test_obs.py proves it on a full run)
        if tracker is None:
            from repro.obs.tracker import NoopTracker
            tracker = NoopTracker()
        self.tracker = tracker
        # deterministic-identity span tracer over the same tracker: span ids
        # hash (run_id, scope, phase); against a NoopTracker every profiler
        # call short-circuits before reading a clock (repro.obs.span)
        from repro.obs.prof import Profiler
        self.prof = Profiler(tracker, run_id=run_id or "serve")
        self._req_spans: Dict[int, object] = {}     # req_id -> request span
        self._queue_spans: Dict[int, object] = {}   # req_id -> queue span
        self._submit_step: Dict[int, int] = {}      # req_id -> submit step
        self.prefill_chunk = prefill_chunk
        self.max_seq = max_seq
        mpps = max_seq // page_size
        layout = PagedLayout(page_size=page_size,
                             n_pages=n_pages or n_slots * mpps,
                             n_slots=n_slots, max_pages_per_slot=mpps)
        self.cache = PagedKVCache(cfg, layout)
        self.sched = FCFSScheduler(n_slots)
        self._slots: Dict[int, _Active] = {}
        self.results: Dict[int, List[int]] = {}
        self.result_logprobs: Dict[int, np.ndarray] = {}
        self.prefill_logits: Dict[int, np.ndarray] = {}
        self._capture = capture_prefill_logits
        self._next_id = 0
        self.decode_steps = 0               # telemetry for tests/benchmarks

        # ----- robustness state (all inert until a knob or fault uses it)
        self.faults = faults
        self.max_queue_depth = max_queue_depth
        self.snapshot_dir, self.snapshot_every = snapshot_dir, snapshot_every
        self.engine_steps = 0               # the deterministic clock: every
        #                                     deadline/fault/snapshot is keyed
        #                                     to this counter, never wall time
        self.preemptions = 0
        self.rejected: Dict[int, str] = {}          # req_id -> shed reason
        self.cancelled: Dict[int, np.ndarray] = {}  # req_id -> partial tokens
        self._deadline: Dict[int, int] = {}         # req_id -> absolute step
        # req_id -> (produced, logprobs) of a preempted request awaiting its
        # recompute-restore re-admission
        self._resume: Dict[int, Tuple[List[int], List[float]]] = {}
        self._stall_until = 0               # decode suppressed before this step
        self._quarantine: List[Tuple[int, List[int]]] = []  # (release, pages)

        self.mesh = mesh
        if mesh is None:
            self._step = _paged_step_fn(cfg)
        else:
            from repro.serve.sharded import (make_sharded_paged_step,
                                             place_on_mesh)
            self.params, self.cache.pools = place_on_mesh(
                cfg, mesh, params, self.cache.pools)
            sharded = make_sharded_paged_step(cfg, mesh, self.params,
                                              self.cache.pools,
                                              prof=self.prof)
            dev = mesh.devices.flat[0]

            def step(*args):
                logits, pools = sharded(*args)
                # Gather logits onto one device before the sampler: a
                # vocab-sharded operand would make log_softmax's sum/max
                # lower as a cross-device reduction whose combine topology
                # depends on TP degree (~1-ulp logprob drift at tp>=2).
                # device_put is pure data movement, so this is bitwise.
                return jax.device_put(logits, dev), pools

            self._step = step
        self._sampler = _sampler_fn(scfg)

        self.spec = None
        if spec_k:
            from repro.serve.spec import Speculator
            self.spec = Speculator(self, spec_k, draft_cfg=draft_cfg,
                                   draft_params=draft_params)
        elif draft_params is not None or draft_cfg is not None:
            raise ValueError("draft_cfg/draft_params require spec_k >= 1")

    # ------------------------------------------------------------ request API
    def submit(self, tokens, *, req_id: Optional[int] = None,
               max_new_tokens: int = 16,
               deadline_steps: Optional[int] = None) -> int:
        """Queue a request. Lower ids are served first (FCFS by id).

        Validates the *whole worst case* up front — total positions vs
        ``max_seq`` and the worst-case page budget vs the pool — raising a
        ``ValueError`` that names the violated limit, so an unfittable
        request can never reach ``_admission_check`` and head-of-line block
        the engine.  ``deadline_steps``: cancel the request (freeing its
        pages immediately) if it has not finished within that many *engine
        steps* from now — a deterministic deadline, never a wall clock.
        """
        if req_id is None:
            req_id = self._next_id
        tokens = tuple(int(t) for t in np.asarray(tokens).reshape(-1))
        if (req_id in self.results or req_id in self.cancelled
                or req_id in self.rejected or any(
                    st.req.id == req_id for st in self._slots.values())):
            # the scheduler only guards pending/active ids; a finished id
            # would silently overwrite its result and corrupt the FCFS clock
            raise ValueError(f"request id {req_id} was already served")
        total = len(tokens) + max_new_tokens
        if total > self.max_seq:
            # ValueError, not assert: user-facing validation must survive -O
            raise ValueError(
                f"request {req_id} needs {total} positions "
                f"({len(tokens)} prompt + {max_new_tokens} new); "
                f"slot capacity is max_seq={self.max_seq}")
        need = self.cache.layout.pages_for(total)
        if need > self.cache.layout.n_pages:
            # FCFS admission head-of-line blocks on an unfittable request
            # forever — reject it at the door instead.
            raise ValueError(
                f"request {req_id} needs {need} pages (worst case) but the "
                f"pool only has n_pages={self.cache.layout.n_pages}; raise "
                f"n_pages or shrink the request")
        if deadline_steps is not None and deadline_steps <= 0:
            raise ValueError(f"deadline_steps must be > 0, got "
                             f"{deadline_steps}")
        if (self.max_queue_depth is not None
                and len(self.sched.pending) >= self.max_queue_depth):
            # deterministic load shedding: queue state is a pure function of
            # the request stream, so the shed set replays identically
            self.rejected[req_id] = "queue_full"
            self._next_id = max(self._next_id, req_id + 1)
            shed = {"request_id": req_id,
                    "queue_depth": self.max_queue_depth}
            if self.prof.armed:
                shed["at_s"] = round(self.prof.now(), 9)
            self.tracker.log("serve_shed", shed)
            raise QueueFull(req_id, self.max_queue_depth)
        self.sched.submit(Request(req_id, tokens, max_new_tokens))
        if deadline_steps is not None:
            self._deadline[req_id] = self.engine_steps + deadline_steps
        self._next_id = max(self._next_id, req_id + 1)   # only after validation
        # spans open only past validation: a shed/invalid request never gets
        # one (its serve_shed mark is the record)
        rs = self.prof.begin("request", scope=f"req:{req_id}",
                             lane=f"req{req_id}", prompt_len=len(tokens))
        if rs is not None:
            self._req_spans[req_id] = rs
            self._queue_spans[req_id] = self.prof.begin(
                "queue", scope=f"req:{req_id}", parent=rs, lane=f"req{req_id}")
            self._submit_step[req_id] = self.engine_steps
        self.tracker.log("serve_submit", {
            "request_id": req_id, "prompt_len": len(tokens),
            "max_new_tokens": max_new_tokens})
        return req_id

    def run(self) -> Dict[int, np.ndarray]:
        """Drive steps until every submitted request finished; return tokens.

        Completed requests only: shed requests are in ``self.rejected`` and
        deadline-cancelled ones in ``self.cancelled``.  When the stream
        drains, any pages still quarantined by an injected exhaustion fault
        are force-released, so a drained engine always has its full pool back
        (the zero-leak invariant the preemption soak asserts).
        """
        while not self.sched.idle:
            self.step()
        self._release_quarantine(self.engine_steps, force=True)
        return {rid: np.asarray(toks, np.int32)
                for rid, toks in self.results.items()}

    # ---------------------------------------------------------------- engine
    def _admission_check(self):
        """Capacity predicate for one admission round.

        Stateful on purpose: ``FCFSScheduler.admit`` probes several pending
        requests against the pool before ``_prefill`` allocates anything, so
        the predicate must count pages claimed by earlier admissions in the
        same round — otherwise two requests that each fit alone but not
        together are both admitted and alloc() hits the 'no mid-flight OOM'
        invariant it exists to protect.
        """
        reserved = 0

        def fits(req: Request) -> bool:
            nonlocal reserved
            need = self.cache.layout.pages_for(
                len(req.tokens) + req.max_new_tokens)
            if need + reserved > self.cache.free_pages:
                return False
            reserved += need        # admit() always takes a fitting request
            return True

        return fits

    def _chunked_prefill(self, slot: int, tokens: np.ndarray,
                         rows: Optional[list] = None,
                         scope: Optional[str] = None):
        """Run ``tokens`` through the paged step in fixed-size chunks, writing
        their K/V into ``slot``'s pages. Returns the last chunk's logits.
        Shared by fresh prefill and preemption-restore recompute — same code
        path, so the invariance-by-chunk-size proof covers both.  ``scope``
        (e.g. ``"req:3"``) keys per-chunk profiler spans."""
        plen, C = len(tokens), self.prefill_chunk
        table = self.cache.device_page_table([slot])     # fixed for the prefill
        logits = None
        for start in range(0, plen, C):
            span = (self.prof.begin("prefill_chunk",
                                    scope=f"{scope}/pos:{start}",
                                    lane=f"slot{slot}")
                    if scope is not None else None)
            pos = np.arange(start, start + C, dtype=np.int32)
            valid = pos < plen
            toks = np.where(valid, tokens[np.minimum(pos, plen - 1)], 0)
            wp, wo = self.cache.write_targets(slot, pos, valid)
            logits, self.cache.pools = self._step(
                self.params, self.cache.pools,
                jnp.asarray(toks)[None], jnp.asarray(pos)[None], table,
                jnp.asarray(wp), jnp.asarray(wo))
            if rows is not None:         # valid rows only, raw dtype (bitwise)
                rows.append(np.asarray(logits[0, : min(C, plen - start)]))
            self.prof.end(span, n_valid=int(valid.sum()))
        return logits

    def _prefill(self, slot: int, req: Request) -> None:
        """Chunked prefill of one request; samples its first token.

        For a request preempted earlier (``_resume`` holds its generated
        prefix), this is the *restore* path: recompute K/V for
        ``prompt + produced[:-1]`` — every position whose K/V the decode loop
        had already written — and keep the emitted tokens as-is.  Nothing is
        re-sampled, so the continuation is bitwise identical to never having
        been preempted.
        """
        lay = self.cache.layout
        self.cache.alloc(slot, lay.pages_for(len(req.tokens) + req.max_new_tokens))
        plen, C = len(req.tokens), self.prefill_chunk
        qs = self._queue_spans.pop(req.id, None)
        self.prof.end(qs, slot=slot, queued_steps=self.engine_steps
                      - self._submit_step.get(req.id, self.engine_steps))
        rspan = self._req_spans.get(req.id)
        resume = self._resume.pop(req.id, None)
        if resume is not None:
            produced, lps = resume
            prefix = np.asarray(list(req.tokens) + list(produced[:-1]),
                                np.int32)
            ps = self.prof.begin("prefill", scope=f"req:{req.id}/restore",
                                 parent=rspan, lane=f"slot{slot}",
                                 step=self.engine_steps)
            self._chunked_prefill(slot, prefix, scope=f"req:{req.id}/restore")
            if self.spec is not None:
                # the drafter's KV over the same prefix, recomputed the same
                # way — so post-restore drafts (and hence round boundaries)
                # replay bitwise (no-op for self-draft: shared pools)
                self.spec.prefill(self, slot, prefix)
            self._slots[slot] = st = _Active(req, list(produced), list(lps))
            self.prof.end(ps, prompt_len=len(prefix), restored=True,
                          tokens_kept=len(produced))
            self.tracker.log("serve_restore", {
                "request_id": req.id, "slot": slot,
                "recomputed_positions": len(prefix),
                "tokens_kept": len(produced)})
            self._finish_check(st)
            return
        ps = self.prof.begin("prefill", scope=f"req:{req.id}", parent=rspan,
                             lane=f"slot{slot}", step=self.engine_steps)
        rows = [] if self._capture else None
        logits = self._chunked_prefill(slot, np.asarray(req.tokens, np.int32),
                                       rows, scope=f"req:{req.id}")
        if self.spec is not None:
            self.spec.prefill(self, slot, np.asarray(req.tokens, np.int32))
        if self._capture:
            self.prefill_logits[req.id] = np.concatenate(rows, axis=0)
        first, first_lp = self._sampler(logits[:, (plen - 1) % C],
                                        jnp.asarray([req.id], jnp.int32),
                                        jnp.asarray([0], jnp.int32))
        self._slots[slot] = st = _Active(req, [int(first[0])],
                                         [float(first_lp[0])])
        if ps is not None:    # TTFT: submit (request-span begin) → first token
            ttft = (self.prof.now() - rspan.begin_s if rspan is not None
                    else None)
            self.prof.end(ps, prompt_len=plen, chunks=-(-plen // C),
                          **({"ttft_s": round(ttft, 9)}
                             if ttft is not None else {}))
        self.tracker.log("serve_prefill", {
            "request_id": req.id, "slot": slot, "prompt_len": plen,
            "chunks": -(-plen // C)})
        self._finish_check(st)

    def _finish_check(self, st: _Active) -> None:
        last = st.produced[-1]
        if ((self.scfg.eos_id is not None and last == self.scfg.eos_id)
                or len(st.produced) >= st.req.max_new_tokens):
            st.done = True

    # ------------------------------------------------------ fault machinery
    def _victim(self) -> Optional[int]:
        """Deterministic preemption victim: the active slot holding the
        highest request id (the youngest stream loses — FCFS fairness), or
        None when nothing is active."""
        if not self._slots:
            return None
        return max(self._slots, key=lambda s: self._slots[s].req.id)

    def _preempt(self, slot: int, reason: str) -> None:
        """Evict one active request: free its pages now, stash its generated
        prefix, and re-queue it for recompute-restore (see ``_prefill``)."""
        st = self._slots.pop(slot)
        self._resume[st.req.id] = (list(st.produced), list(st.logprobs))
        self.cache.free_slot(slot)
        self.sched.release(slot)
        self.sched.submit(st.req)       # re-enters FCFS at its original id
        self.preemptions += 1
        data = {"request_id": st.req.id, "slot": slot, "reason": reason,
                "tokens_kept": len(st.produced)}
        if self.prof.armed:             # timeline instant + a fresh queue
            data["at_s"] = round(self.prof.now(), 9)   # span for the re-wait
            self._submit_step[st.req.id] = self.engine_steps
            self._queue_spans[st.req.id] = self.prof.begin(
                "queue", scope=f"req:{st.req.id}/preempt{self.preemptions}",
                parent=self._req_spans.get(st.req.id),
                lane=f"req{st.req.id}")
        self.tracker.log("serve_preempt", data, step=self.engine_steps)

    def _apply_faults(self, step_idx: int) -> None:
        """Consume this step's scheduled faults. May raise ``EngineCrash``."""
        from repro.faults import EngineCrash
        for f in self.faults.step_faults(step_idx):
            if f.kind == "crash":
                if self.faults.consume_crash(f):
                    self.faults.record(f, engine_step=step_idx)
                    raise EngineCrash(step_idx)
            elif f.kind == "decode_stall":
                self._stall_until = max(self._stall_until, step_idx + f.arg)
                self.faults.record(f, engine_step=step_idx,
                                   stalled_until=self._stall_until)
            elif f.kind == "revoke_slot":
                revoked = []
                for _ in range(max(1, f.arg)):
                    victim = self._victim()
                    if victim is None:
                        break
                    revoked.append(self._slots[victim].req.id)
                    self._preempt(victim, reason="slot_revoked")
                self.faults.record(f, engine_step=step_idx, victims=revoked)
            elif f.kind == "pool_exhaust":
                want = min(f.arg, self.cache.layout.n_pages)
                evicted = []
                while self.cache.free_pages < want:
                    victim = self._victim()
                    if victim is None:
                        break
                    evicted.append(self._slots[victim].req.id)
                    self._preempt(victim, reason="pool_exhausted")
                take = min(want, self.cache.free_pages)
                pages = self.cache.quarantine(take)
                if pages:
                    self._quarantine.append((step_idx + f.duration, pages))
                self.faults.record(f, engine_step=step_idx, pages=len(pages),
                                   victims=evicted)

    def _release_quarantine(self, step_idx: int, force: bool = False) -> None:
        keep = []
        for release, pages in self._quarantine:
            if force or release <= step_idx:
                self.cache.release_quarantine(pages)
            else:
                keep.append((release, pages))
        self._quarantine = keep

    def _cancel_expired(self, step_idx: int) -> None:
        """Cancel every request whose step-deadline has passed: pending ones
        drop from the queue, active ones free slot+pages immediately; partial
        tokens land in ``self.cancelled`` (never ``results``)."""
        if not self._deadline:
            return
        for rid in sorted(self.sched.pending):
            if self._deadline.get(rid, step_idx + 1) <= step_idx:
                del self.sched.pending[rid]
                produced, _ = self._resume.pop(rid, ([], []))
                self.cancelled[rid] = np.asarray(produced, np.int32)
                del self._deadline[rid]
                self.prof.end(self._queue_spans.pop(rid, None),
                              cancelled=True)
                self.prof.end(self._req_spans.pop(rid, None),
                              cancelled=True, n_tokens=len(produced))
                self.tracker.log("serve_cancel", {
                    "request_id": rid, "where": "pending",
                    "tokens_kept": len(produced)}, step=step_idx)
        for slot in sorted(self._slots):
            rid = self._slots[slot].req.id
            if self._deadline.get(rid, step_idx + 1) <= step_idx:
                st = self._slots.pop(slot)
                self.cancelled[rid] = np.asarray(st.produced, np.int32)
                self.cache.free_slot(slot)          # immediate reclamation
                self.sched.release(slot)
                del self._deadline[rid]
                self.prof.end(self._req_spans.pop(rid, None),
                              cancelled=True, n_tokens=len(st.produced))
                self.tracker.log("serve_cancel", {
                    "request_id": rid, "where": "active",
                    "tokens_kept": len(st.produced)}, step=step_idx)

    # ----------------------------------------------------------------- step
    def step(self) -> None:
        """One engine step: faults → deadline sweep → admit+prefill → one
        batched decode step → reap.  ``engine_steps`` is the deterministic
        clock every fault/deadline/snapshot keys to."""
        step_idx = self.engine_steps
        if self.faults is not None:
            self._apply_faults(step_idx)            # may raise EngineCrash
        self._release_quarantine(step_idx)
        self._cancel_expired(step_idx)
        for slot, req in self.sched.admit(self._admission_check()):
            self._prefill(slot, req)

        stalled = step_idx < self._stall_until
        live = ([] if stalled
                else [s for s, st in self._slots.items() if not st.done])
        if live and self.spec is not None:
            # speculative round: draft spec_k, verify, commit the accepted
            # prefix — up to spec_k+1 tokens per slot per engine step, every
            # one bitwise identical to the plain path (repro.serve.spec)
            span = self.prof.begin("spec_round", scope=f"step:{step_idx}",
                                   lane="engine", step=step_idx)
            self.spec.round(self, live)
            self.prof.end(span, live_slots=len(live))
        elif live:
            span = self.prof.begin("decode", scope=f"step:{step_idx}",
                                   lane="engine", step=step_idx)
            lay = self.cache.layout
            n = lay.n_slots
            toks = np.zeros((n, 1), np.int32)
            pos = np.zeros((n, 1), np.int32)
            wp = np.full(n, lay.trash_page, np.int32)
            wo = np.arange(n, dtype=np.int32) % lay.page_size
            rids = np.zeros(n, np.int32)
            steps = np.zeros(n, np.int32)
            for s in live:
                st = self._slots[s]
                toks[s, 0] = st.produced[-1]
                pos[s, 0] = st.next_pos
                wp[s], wo[s] = (a[0] for a in self.cache.write_targets(
                    s, np.asarray([st.next_pos]), np.asarray([True])))
                rids[s] = st.req.id
                steps[s] = len(st.produced)
            logits, self.cache.pools = self._step(
                self.params, self.cache.pools, jnp.asarray(toks),
                jnp.asarray(pos), self.cache.device_page_table(),
                jnp.asarray(wp), jnp.asarray(wo))
            self.decode_steps += 1
            nxt, lps = self._sampler(logits[:, 0], jnp.asarray(rids),
                                     jnp.asarray(steps))
            nxt, lps = np.asarray(nxt), np.asarray(lps)
            for s in live:
                st = self._slots[s]
                st.produced.append(int(nxt[s]))
                st.logprobs.append(float(lps[s]))
                self._finish_check(st)
            self.prof.end(span, live_slots=len(live), committed=len(live))
            self.tracker.log("serve_decode", {"live_slots": len(live)},
                             step=self.decode_steps)

        for s in [s for s, st in self._slots.items() if st.done]:
            st = self._slots.pop(s)
            self.results[st.req.id] = st.produced
            self.result_logprobs[st.req.id] = np.asarray(st.logprobs,
                                                         np.float32)
            self._deadline.pop(st.req.id, None)
            self.cache.free_slot(s)
            self.sched.release(s)
            self.prof.end(self._req_spans.pop(st.req.id, None),
                          n_tokens=len(st.produced), slot=s)
            self._submit_step.pop(st.req.id, None)
            self.tracker.log("serve_done", {
                "request_id": st.req.id, "slot": s,
                "n_tokens": len(st.produced),
                "decode_steps": self.decode_steps})

        self.engine_steps = step_idx + 1
        if (self.snapshot_dir is not None and self.snapshot_every
                and self.engine_steps % self.snapshot_every == 0):
            self.save_snapshot()

    # ------------------------------------------------------ snapshot/restore
    def save_snapshot(self, directory: Optional[str] = None) -> int:
        """Persist the full engine state (scheduler, page tables, per-request
        sampling state, emitted tokens, KV pools) at the current engine step
        through the manifest-v2 digest machinery. Returns the snapshot step."""
        from repro.serve import snapshot as SN
        return SN.save_engine_snapshot(self, directory or self.snapshot_dir)

    @classmethod
    def from_snapshot(cls, directory: str, cfg, params, *,
                      step: Optional[int] = None, faults=None, tracker=None,
                      mesh=None, draft_cfg=None,
                      draft_params=None) -> "ContinuousEngine":
        """Rebuild an engine from a snapshot (latest by default) and resume:
        every stream that was in flight completes bitwise identically to an
        uncrashed run (README §Robustness).  A snapshot taken with a
        separate drafter requires ``draft_params`` (and ``draft_cfg`` if one
        was supplied originally) — drafter params are never serialized, like
        target params; the drafter's KV pools *are* in the snapshot."""
        from repro.serve import snapshot as SN
        return SN.restore_engine(directory, cfg, params, step=step,
                                 faults=faults, tracker=tracker, mesh=mesh,
                                 draft_cfg=draft_cfg,
                                 draft_params=draft_params)
