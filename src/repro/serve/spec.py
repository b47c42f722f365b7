"""Verified speculative decoding for the continuous engine (ROADMAP item 2).

Draft-and-verify with **exact acceptance**: a drafter proposes ``k`` tokens
per active slot, the target scores the proposals, and a draft is accepted iff
it equals the token the plain (non-speculative) engine would have sampled —
the keyed sample ``fold_in(fold_in(key(seed), request_id), token_index)``
over the target's logits, drawn by exactly the sampler the plain decode path
uses (:func:`repro.serve.engine._sample_rows`).  Acceptance is therefore a
*comparison*, not a probabilistic correction: the committed stream is
bitwise identical to the non-speculative stream **by construction**, greedy
and seeded sampling alike (tests/test_spec_decode.py).

Why the verify pass is a scan of (n_slots, 1) steps, not one wide chunk
--------------------------------------------------------------------------
Scoring all k+1 positions in a single ``(n_slots, k+1)`` chunked-prefill-
style ``paged_attention`` pass is numerically *almost* right but not
bitwise: XLA CPU gemm accumulation order depends on the M dimension, so
chunk-shaped logits drift ~1e-4 from the (n_slots, 1) decode shape — tokens
survive (argmax is robust) but the logprob contract does not.  Instead the
round stays in the engine's proven-bitwise decode shape and recovers the
throughput from *dispatch fusion*: the whole round — k drafter steps and
k+1 verify steps, each an (n_slots, 1) ``paged_step`` with in-scan keyed
sampling — is one ``lax.scan`` inside one jit, so one device dispatch and
one host sync replace 2(k+1) of them.  The spike measurement on the reduced
config: ~3.7x tokens/dispatch at k=4 (recorded in BENCH_serve.json).

Self-draft (``draft_params is None``) is the degenerate case: drafter and
target are the same model, so the self-feeding scan *is* simultaneously the
draft and the verify — each step samples the plain-path token and feeds it
forward.  Acceptance is structurally 1.0 and the round costs k+1 model
steps for k+1 tokens (zero duplicated compute).  A separate drafter runs
its own self-feeding scan over its own KV pools (same page table, same
deterministic allocator), then the target verifies teacher-forced.

Cache discipline under rejection
--------------------------------
A rejected round leaves stale K/V (computed from rejected draft tokens) at
positions beyond the accepted length, in both target and drafter pools.  No
rollback pass is needed: the next round starts at the first uncommitted
position and every scan step *writes its position's K/V before attending*,
in ascending position order, so every stale entry is overwritten before any
query can read it (positions above the query index are masked to exact zero
by the kernel).  Reclamation is therefore deterministic overwrite, not
bookkeeping — the same self-healing argument the preemption-restore
recompute already relies on.

Admission already reserves the worst case: the per-slot clamp
``k_s = min(k, max_new - produced - 1)`` keeps every real K/V write at a
position ``<= prompt_len + max_new - 2``, inside the
``pages_for(prompt_len + max_new)`` reservation the scheduler made at
admission (scan steps beyond ``k_s`` write to the trash page with distinct
offsets, like pad rows everywhere else).

Under a TP ``mesh`` the round falls back to sequential calls of the
engine's sharded step + standalone sampler (the plain decode code path,
teacher-forced) — bitwise by construction, no dispatch fusion; a separate
drafter still drafts via its own single-device fused scan.  Speculation
under TP is a capacity/compatibility mode, not a speedup.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.dist import fold
from repro.models import transformer as T


@functools.lru_cache(maxsize=None)
def _spec_scan_fn(cfg, scfg, n_steps: int, teacher_forced: bool):
    """One fused speculative phase: ``n_steps`` (n_slots, 1) paged decode
    steps in a single jitted ``lax.scan``, each sampling with the engine's
    keyed row sampler (:func:`repro.serve.engine._sample_rows` — literally
    the same traced function as the standalone sampler, so in-scan samples
    are bitwise identical to plain-path samples).

    ``teacher_forced=False``: step ``l`` feeds the previous step's sample
    (step 0 feeds ``tok0``) — the drafter's proposal scan, and the entire
    round for self-draft.  ``teacher_forced=True``: step ``l`` feeds
    ``feed[l]`` (the draft sequence) — the target's verify scan.

    Returns ``(tokens (n, n_steps), logprobs (n, n_steps), pools)``.
    """
    from repro.serve.engine import _sample_rows

    def run(params, pools, tok0, feed, pos, table, wp, wo, rids, steps0):
        # tok0 (n, 1); feed/pos/wp/wo (n_steps, n); rids/steps0 (n,)
        def body(carry, xs):
            tok, pools = carry
            l, feed_l, pos_l, wp_l, wo_l = xs
            inp = feed_l[:, None] if teacher_forced else tok
            logits, pools = T.paged_step(params, pools, inp, pos_l[:, None],
                                         table, wp_l, wo_l, cfg=cfg)
            nxt, lp = _sample_rows(logits[:, 0], rids, steps0 + l, scfg)
            return (nxt[:, None], pools), (nxt, lp)

        (_, pools), (toks, lps) = jax.lax.scan(
            body, (tok0, pools),
            (jnp.arange(n_steps), feed, pos, wp, wo))
        return toks.T, lps.T, pools

    return fold.exact_jit(run)


class Speculator:
    """Per-engine speculative-decoding state: drafter pairing, drafter KV
    pools, the fused round, and acceptance telemetry.

    ``draft_params is None`` selects self-draft (drafter ≡ target, shared
    pools).  A separate drafter must be a paged-servable config with the
    same vocabulary as the target; it maintains its own KV pools over the
    same page-table geometry, chunk-prefilled at admission and recomputed
    on preemption-restore exactly like the target's.
    """

    def __init__(self, eng, k: int, draft_cfg=None, draft_params=None):
        if k < 1:
            raise ValueError(f"spec_k must be >= 1, got {k}")
        self.k = int(k)
        self.self_draft = draft_params is None
        self.dcfg = eng.cfg if self.self_draft else (draft_cfg or eng.cfg)
        self.dparams = eng.params if self.self_draft else draft_params
        if not self.self_draft:
            if not T.supports_paged(self.dcfg):
                raise ValueError("drafter must be a paged-servable "
                                 "(decoder-only, attention-only) config")
            if self.dcfg.vocab != eng.cfg.vocab:
                raise ValueError(
                    f"drafter vocab {self.dcfg.vocab} != target vocab "
                    f"{eng.cfg.vocab}: speculative acceptance compares token "
                    "ids, so drafter and target must share a vocabulary")
            lay = eng.cache.layout
            self.pools = T.init_paged_cache(self.dcfg, lay.n_pages + 1,
                                            lay.page_size)
        else:
            self.pools = None           # alias: target pools are the drafter's
        # telemetry: drafted counts proposals, accepted counts verified
        # matches, truncated counts proposals never evaluated because the
        # stream finished (EOS/max_new) before their position
        self.rounds = 0
        self.drafted = 0
        self.accepted = 0
        self.truncated = 0
        self.draft_steps = 0            # drafter model steps dispatched

    # ------------------------------------------------------------- telemetry
    def acceptance_rate(self) -> float:
        """Accepted / evaluated proposals (1.0 for self-draft by
        construction — the CI smoke gate)."""
        evaluated = self.drafted - self.truncated
        return self.accepted / evaluated if evaluated else 1.0

    # -------------------------------------------------------------- prefill
    def prefill(self, eng, slot: int, tokens: np.ndarray) -> None:
        """Chunk-prefill the drafter's KV for ``tokens`` into ``slot``'s
        pages (separate drafter only; self-draft shares the target pools).
        Same chunking discipline and write targets as the engine's prefill,
        so drafter state after preemption-restore recompute is bitwise
        identical to never having been preempted."""
        if self.self_draft:
            return
        # the drafter always runs single-device, whether or not the engine's
        # own step is mesh-sharded
        from repro.serve.engine import _paged_step_fn
        step = _paged_step_fn(self.dcfg)
        plen, C = len(tokens), eng.prefill_chunk
        table = eng.cache.device_page_table([slot])
        for start in range(0, plen, C):
            pos = np.arange(start, start + C, dtype=np.int32)
            valid = pos < plen
            toks = np.where(valid, tokens[np.minimum(pos, plen - 1)], 0)
            wp, wo = eng.cache.write_targets(slot, pos, valid)
            _, self.pools = step(
                self.dparams, self.pools,
                jnp.asarray(toks)[None], jnp.asarray(pos)[None], table,
                jnp.asarray(wp), jnp.asarray(wo))
            self.draft_steps += 1

    # ---------------------------------------------------------------- round
    def round(self, eng, live: List[int]) -> None:
        """One speculative round over the live slots: draft k, verify k+1,
        commit the accepted prefix + one corrected/bonus token per slot."""
        lay = eng.cache.layout
        n, k = lay.n_slots, self.k
        S = k + 1
        tok0 = np.zeros((n, 1), np.int32)
        feed = np.zeros((S, n), np.int32)
        pos = np.zeros((S, n), np.int32)
        wp = np.full((S, n), lay.trash_page, np.int32)
        wo = np.tile(np.arange(n, dtype=np.int32) % lay.page_size, (S, 1))
        rids = np.zeros(n, np.int32)
        steps0 = np.zeros(n, np.int32)
        k_s: Dict[int, int] = {}
        for s in live:
            st = eng._slots[s]
            m = len(st.produced)
            ks = min(k, st.req.max_new_tokens - m - 1)      # per-slot clamp
            k_s[s] = ks
            p0 = st.next_pos
            lay.check_spec_write(len(st.req.tokens), st.req.max_new_tokens,
                                 p0 + ks)
            tok0[s, 0] = st.produced[-1]
            # pad steps (l > ks) re-read position p0+ks and write to trash:
            # in-bounds everywhere, outputs ignored by the commit loop
            pos[:, s] = p0 + np.minimum(np.arange(S), ks)
            real = np.arange(ks + 1)
            pages, offs = eng.cache.write_targets(
                s, p0 + real, np.ones(ks + 1, bool))
            wp[real, s], wo[real, s] = pages, offs
            rids[s] = st.req.id
            steps0[s] = m

        table = eng.cache.device_page_table()
        if self.self_draft:
            toks, lps, pools = self._self_feed(eng, eng.params,
                                               eng.cache.pools, tok0, feed,
                                               pos, table, wp, wo, rids,
                                               steps0, sharded=eng.mesh
                                               is not None)
            eng.cache.pools = pools
            drafts = toks[:, :k]
        else:
            # separate drafter: the two scans get their own profiler spans
            # (the engine wraps the whole round in ``spec_round``); self-draft
            # fuses draft+verify into one scan, so only the round span exists
            with eng.prof.span("spec_draft", scope=f"step:{eng.engine_steps}",
                               lane="engine", k=k):
                dtoks, _, self.pools = self._self_feed(
                    eng, self.dparams, self.pools, tok0, feed, pos, table, wp,
                    wo, rids, steps0, sharded=False)
            drafts = dtoks[:, :k]
            self.draft_steps += S
            feed[0], feed[1:] = tok0[:, 0], drafts.T
            with eng.prof.span("spec_verify",
                               scope=f"step:{eng.engine_steps}",
                               lane="engine", k=k):
                toks, lps, pools = self._verify(eng, feed, pos, table, wp, wo,
                                                rids, steps0, tok0)
            eng.cache.pools = pools
        eng.decode_steps += 1           # one verify dispatch per round

        # ---- exact acceptance: commit while draft == the plain-path sample
        committed = matched = evaluated = 0
        for s in live:
            st = eng._slots[s]
            ks = k_s[s]
            for l in range(ks + 1):
                st.produced.append(int(toks[s, l]))
                st.logprobs.append(float(lps[s, l]))
                committed += 1
                eng._finish_check(st)
                if st.done:
                    break
                if l < ks:
                    evaluated += 1
                    if int(drafts[s, l]) != int(toks[s, l]):
                        break
                    matched += 1
            self.drafted += ks
        self.rounds += 1
        self.accepted += matched
        self.truncated += sum(k_s.values()) - evaluated
        eng.tracker.log("serve_spec_round", {
            "live_slots": len(live), "k": k, "committed": committed,
            "accepted": matched, "evaluated": evaluated},
            step=eng.engine_steps)

    # ------------------------------------------------------------ internals
    def _self_feed(self, eng, params, pools, tok0, feed, pos, table, wp, wo,
                   rids, steps0, sharded: bool):
        """Self-feeding phase: each step samples and feeds its own token.
        Fused scan on a single device; sequential plain-shaped steps through
        the engine's sharded step under a mesh (bitwise either way)."""
        S = self.k + 1
        if not sharded:
            cfg = eng.cfg if params is eng.params else self.dcfg
            fn = _spec_scan_fn(cfg, eng.scfg, S, False)
            toks, lps, pools = fn(params, pools, jnp.asarray(tok0),
                                  jnp.asarray(feed), jnp.asarray(pos), table,
                                  jnp.asarray(wp), jnp.asarray(wo),
                                  jnp.asarray(rids), jnp.asarray(steps0))
            return np.asarray(toks), np.asarray(lps), pools
        return self._sequential(eng, pools, tok0, None, pos, table, wp, wo,
                                rids, steps0)

    def _verify(self, eng, feed, pos, table, wp, wo, rids, steps0, tok0):
        """Teacher-forced verify of the draft sequence on the target."""
        if eng.mesh is None:
            fn = _spec_scan_fn(eng.cfg, eng.scfg, self.k + 1, True)
            toks, lps, pools = fn(eng.params, eng.cache.pools,
                                  jnp.asarray(tok0), jnp.asarray(feed),
                                  jnp.asarray(pos), table, jnp.asarray(wp),
                                  jnp.asarray(wo), jnp.asarray(rids),
                                  jnp.asarray(steps0))
            return np.asarray(toks), np.asarray(lps), pools
        return self._sequential(eng, eng.cache.pools, None, feed, pos, table,
                                wp, wo, rids, steps0)

    def _sequential(self, eng, pools, tok0, feed, pos, table, wp, wo, rids,
                    steps0):
        """Mesh fallback: the same round as S sequential (n,1) calls of the
        engine's (sharded) step + standalone sampler — the plain decode code
        path, so bitwise by construction.  ``feed=None`` self-feeds."""
        S = self.k + 1
        cur = jnp.asarray(tok0) if feed is None else None
        toks = np.zeros((pos.shape[1], S), np.int32)
        lps = np.zeros((pos.shape[1], S), np.float32)
        for l in range(S):
            inp = cur if feed is None else jnp.asarray(feed[l])[:, None]
            logits, pools = eng._step(
                eng.params, pools, inp, jnp.asarray(pos[l])[:, None], table,
                jnp.asarray(wp[l]), jnp.asarray(wo[l]))
            nxt, lp = eng._sampler(logits[:, 0], jnp.asarray(rids),
                                   jnp.asarray(steps0 + l))
            toks[:, l], lps[:, l] = np.asarray(nxt), np.asarray(lp)
            if feed is None:
                cur = jnp.asarray(toks[:, l : l + 1])
        return toks, lps, pools
