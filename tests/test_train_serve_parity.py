"""Train ≡ serve bitwise parity: the training forward IS the prefill.

``ModelConfig.canonical_reductions = N`` runs the training-side ``forward``
under the :mod:`repro.dist.fold` discipline — attention walks the literal
paged-KV serve kernel over N-token pages and the row-parallel projections
(wo, w_down) reduce in the canonical virtual-shard order.  The contract:
those logits are **bitwise equal** to ``ContinuousEngine`` chunked prefill
at ``page_size=N``, per prompt position, for every architecture — packed or
unpacked batches, any GQA group.  The same fact is recorded as the
``train_serve_parity`` cell of ``repro.verify.lifecycle.MATRIX`` in CI's
digest_conformance.json.

Everything here is ``assert_array_equal`` on float32-cast logits — no
tolerances.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.configs import registry
from repro.dist import fold
from repro.launch.train import build
from repro.models import transformer as T
from repro.serve.engine import ContinuousEngine
from repro.train import step as S
from repro.verify import lifecycle as L

PAGE = 8
PROMPT_LENS = (5, 13, 32, 7)
ARCHS = ("stablelm-1.6b", "qwen1.5-110b", "mistral-nemo-12b")


def _prompts(cfg, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, cfg.vocab, size=n).tolist() for n in PROMPT_LENS]


def _serve_prefill(cfg, params, prompts):
    eng = ContinuousEngine(cfg, params, n_slots=2, max_seq=64,
                           page_size=PAGE, prefill_chunk=16,
                           capture_prefill_logits=True)
    for i, p in enumerate(prompts):
        eng.submit(p, req_id=i, max_new_tokens=1)
    eng.run()
    return eng


def _train_fwd(cfg):
    """The canonical train forward, compiled as the trainer compiles its
    step (``train.step.jit``)."""
    pcfg = cfg.replace(canonical_reductions=PAGE)
    return S.jit(pcfg, lambda pr, b: T.forward(pr, b, pcfg)[0])


@pytest.mark.parametrize("arch", ARCHS)
def test_unpacked_parity(arch):
    """Per-arch (GQA ratios 1 and 4 among them): train forward logits equal
    engine chunked-prefill logits bitwise at every prompt position."""
    cfg = registry.get(arch).reduced()
    params = T.init(cfg, jax.random.PRNGKey(0))
    prompts = _prompts(cfg)
    eng = _serve_prefill(cfg, params, prompts)
    fwd = _train_fwd(cfg)
    for i, p in enumerate(prompts):
        toks = jnp.asarray(np.asarray(p, np.int32)[None])
        logits = np.asarray(fwd(params, {"tokens": toks}))[0][: len(p)]
        np.testing.assert_array_equal(
            logits.astype(np.float32),
            eng.prefill_logits[i].astype(np.float32),
            err_msg=f"{arch} req {i}")


@pytest.mark.parametrize("kv_heads", [4, 2])
def test_gqa_groups_parity(kv_heads):
    """GQA groups 1 and 2 via n_kv_heads overrides: parity holds when query
    heads share kv heads (the serve kernel regroups, the train path masks)."""
    cfg = registry.get("stablelm-1.6b").reduced(n_kv_heads=kv_heads)
    params = T.init(cfg, jax.random.PRNGKey(1))
    prompts = _prompts(cfg, seed=1)
    eng = _serve_prefill(cfg, params, prompts)
    fwd = _train_fwd(cfg)
    for i, p in enumerate(prompts):
        toks = jnp.asarray(np.asarray(p, np.int32)[None])
        logits = np.asarray(fwd(params, {"tokens": toks}))[0][: len(p)]
        np.testing.assert_array_equal(
            logits.astype(np.float32),
            eng.prefill_logits[i].astype(np.float32),
            err_msg=f"kv={kv_heads} req {i}")


def test_packed_parity():
    """A packed row (two documents, per-doc RoPE restart, segment-masked
    attention) produces, per document, the same logits the engine produces
    serving each document as its own request."""
    cfg = registry.get("stablelm-1.6b").reduced()
    params = T.init(cfg, jax.random.PRNGKey(0))
    rng = np.random.RandomState(2)
    docs = [rng.randint(1, cfg.vocab, size=n).tolist() for n in (7, 9)]
    pk = cfg.replace(packed_inputs=True, canonical_reductions=PAGE)
    toks = np.concatenate(docs).astype(np.int32)[None]
    poss = np.concatenate(
        [np.arange(len(d)) for d in docs]).astype(np.int32)[None]
    segs = np.concatenate(
        [np.full(len(d), j + 1) for j, d in enumerate(docs)]
    ).astype(np.int32)[None]
    packed = np.asarray(S.jit(pk, lambda pr, b: T.forward(pr, b, pk)[0])(
        params, {"tokens": jnp.asarray(toks), "positions": jnp.asarray(poss),
                 "segment_ids": jnp.asarray(segs)}))[0]
    eng = _serve_prefill(cfg, params, docs)
    off = 0
    for j, d in enumerate(docs):
        np.testing.assert_array_equal(
            packed[off: off + len(d)].astype(np.float32),
            eng.prefill_logits[j].astype(np.float32),
            err_msg=f"doc {j}")
        off += len(d)


def test_windowed_serve_equals_windowed_train_generation():
    """Regression for the paged sliding-window path (it used to refuse
    ``attn_window`` loudly): greedy engine decode under a window equals
    teacher-forced argmax generation from the canonical train forward."""
    cfg = registry.get("stablelm-1.6b").reduced().replace(attn_window=8)
    params = T.init(cfg, jax.random.PRNGKey(0))
    prompts = _prompts(cfg)
    eng = ContinuousEngine(cfg, params, n_slots=4, max_seq=64,
                           page_size=PAGE, prefill_chunk=16)
    for i, p in enumerate(prompts):
        eng.submit(p, req_id=i, max_new_tokens=6)
    served = eng.run()
    fwd = _train_fwd(cfg)
    for i, p in enumerate(prompts):
        seq = list(p)
        for _ in range(6):
            lg = np.asarray(fwd(params, {
                "tokens": jnp.asarray(np.asarray(seq, np.int32)[None])}))[0]
            seq.append(int(np.argmax(lg[len(seq) - 1].astype(np.float32))))
        np.testing.assert_array_equal(
            np.asarray(seq[len(p):], np.int32), served[i],
            err_msg=f"req {i}")


def test_canonical_mode_off_by_default():
    """canonical_reductions=0 keeps the fused training path: same argmax
    (sanity) but the mode flag is what parity relies on, so assert the field
    default and that the canonical forward actually differs in bits from the
    fused one (the contract is *with the engine*, not with fused XLA)."""
    cfg = registry.get("stablelm-1.6b").reduced()
    assert cfg.canonical_reductions == 0
    params = T.init(cfg, jax.random.PRNGKey(0))
    toks = jnp.asarray(np.arange(1, 17, dtype=np.int32)[None])
    fused = np.asarray(
        jax.jit(lambda pr, b: T.forward(pr, b, cfg)[0])(
            params, {"tokens": toks}))
    canon = np.asarray(_train_fwd(cfg)(params, {"tokens": toks}))
    np.testing.assert_array_equal(np.argmax(fused, -1), np.argmax(canon, -1))


def test_trainer_compiles_canonical_mode_like_the_engine(monkeypatch):
    """The forward these tests compare is compiled by the trainer's own
    policy: ``build`` goes through ``fold.exact_jit``, as the engine's paged
    step does, exactly when the config is serve-canonical."""
    seen = []
    real = fold.exact_jit
    monkeypatch.setattr(fold, "exact_jit",
                        lambda f, **kw: seen.append(kw) or real(f, **kw))
    cfg = registry.get("stablelm-1.6b").reduced()
    build(cfg, S.TrainConfig())
    assert seen == []
    build(cfg.replace(canonical_reductions=PAGE), S.TrainConfig())
    assert seen == [{"donate_argnums": (0,)}]


def test_lifecycle_parity_cell_conformant():
    """The MATRIX cell CI records in digest_conformance.json passes here."""
    report = L.run_cell("train_serve_parity")
    assert report["conformant"], report["first_divergence"]
    for arch in L.PARITY_ARCHS:
        assert report["heads"][f"{arch}/train"] == \
            report["heads"][f"{arch}/serve"], arch
