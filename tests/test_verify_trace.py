"""Unit tests for repro.verify.trace — the jaxpr nondeterminism auditor."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.core import determinism as det
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.train import optimizer as O
from repro.train import step as S
from repro.verify import trace


def _codes(findings):
    return sorted(f.code for f in findings)


# ---------------------------------------------------------------- scatters
def test_flags_unordered_scatter_add():
    def f(x, idx, y):
        return x.at[idx].add(y)

    findings = trace.audit_fn(f, jnp.zeros(8), jnp.array([1, 1, 2]),
                              jnp.ones(3))
    assert _codes(findings) == ["unordered-scatter"]


def test_unique_scatters_pass_duplicate_capable_overwrite_flagged():
    def unique_add(x, idx, y):
        return x.at[idx].add(y, unique_indices=True)

    def unique_set(x, idx, y):
        return x.at[idx].set(y, unique_indices=True)

    def dup_set(x, idx, y):
        return x.at[idx].set(y)   # which duplicate wins is backend-defined

    args = (jnp.zeros(8), jnp.array([1, 3, 2]), jnp.ones(3))
    assert trace.audit_fn(unique_add, *args) == []
    assert trace.audit_fn(unique_set, *args) == []
    assert _codes(trace.audit_fn(dup_set, *args)) == ["unordered-scatter"]


def test_scatter_inside_scan_is_found():
    """The walker must recurse into control-flow sub-jaxprs."""
    def f(x, idx):
        def body(carry, _):
            return carry.at[idx].add(1.0), None   # idx has duplicates
        out, _ = jax.lax.scan(body, x, jnp.arange(3))
        return out

    findings = trace.audit_fn(f, jnp.zeros(8), jnp.array([1, 1, 2]))
    assert _codes(findings) == ["unordered-scatter"]


# -------------------------------------------------------------------- psum
def _shard1(fn):
    from jax.sharding import Mesh, PartitionSpec as P
    mesh = Mesh(np.array(jax.devices()[:1]), ("x",))
    return jax.shard_map(fn, mesh=mesh, in_specs=(P("x"),), out_specs=P(None),
                         check_vma=False)


def test_flags_plain_psum_blesses_ring_ordered():
    plain = _shard1(lambda v: jax.lax.psum(v, "x"))
    ring = _shard1(lambda v: det.ring_ordered_psum(v[0], "x"))
    x = jnp.ones((1, 4))
    assert _codes(trace.audit_fn(plain, x)) == ["unordered-psum"]
    assert trace.audit_fn(ring, x) == []


def test_generic_where_masked_psum_is_not_blessed():
    """Only the axis_index one-hot broadcast idiom is blessed: a psum of a
    value masked by an arbitrary predicate still re-associates with topology
    and must be flagged (regression for a false negative where any select_n
    producer passed)."""
    def masked(v):
        pad = jnp.where(v > 0, v, jnp.zeros_like(v))   # data mask, not 1-hot
        return jax.lax.psum(pad, "x")

    findings = trace.audit_fn(_shard1(masked), jnp.ones((1, 4)))
    assert _codes(findings) == ["unordered-psum"]


def test_allow_suppresses_codes():
    plain = _shard1(lambda v: jax.lax.psum(v, "x"))
    assert trace.audit_fn(plain, jnp.ones((1, 4)),
                          allow=["unordered-psum"]) == []


# -------------------------------------------------- precision / sort rules
def test_flags_nonstandard_and_mismatched_reduce_precision():
    def nonstd(x):
        return jax.lax.reduce_precision(x, 6, 9)

    def mismatched(x):
        a = jax.lax.reduce_precision(x, 8, 7)       # bf16
        b = jax.lax.reduce_precision(x, 5, 10)      # f16
        return a + b

    assert _codes(trace.audit_fn(nonstd, jnp.ones(4))) == \
        ["nonstandard-reduce-precision"]
    assert _codes(trace.audit_fn(mismatched, jnp.ones(4))) == \
        ["reduce-precision-mismatch"]


def test_flags_unstable_sort():
    findings = trace.audit_fn(
        lambda x: jax.lax.sort(x, is_stable=False), jnp.ones(4))
    assert _codes(findings) == ["unstable-sort"]
    assert trace.audit_fn(jnp.sort, jnp.ones(4)) == []


# ------------------------------------------------------- train-step oracle
def _train_step_findings(**reduced_kw):
    cfg = registry.get("stablelm-1.6b").reduced(**reduced_kw)
    tcfg = S.TrainConfig(opt=O.OptConfig(total_steps=10))
    state = S.init_state(cfg, tcfg, jax.random.PRNGKey(0))
    data = SyntheticLM(DataConfig(seed=0, batch=2, seq=16, vocab=cfg.vocab))
    return trace.audit_fn(S.make_train_step(cfg, tcfg), state, data.batch(0))


def test_default_train_step_is_clean():
    """The repo's standing contract: the shipped train step lowers with no
    nondeterminism-prone primitives (the embedding backward is a stable
    sort, a fixed-order segmented sum and gathers, not a scatter-add)."""
    assert _train_step_findings() == []


def test_seeded_nondeterministic_scatter_is_caught():
    """Flipping det_embed_grad restores the gather-gradient scatter-add — the
    auditor must catch the regression."""
    findings = _train_step_findings(det_embed_grad=False)
    assert "unordered-scatter" in _codes(findings)


def test_lint_cli_clean_and_dirty(capsys):
    assert trace.main(["--arch", "stablelm-1.6b"]) == 0
    assert "clean" in capsys.readouterr().out


def _zipf_tokens(rng, shape, vocab):
    return np.minimum(rng.zipf(1.3, shape) - 1, vocab - 1)


EMBED_STREAMS = {
    "uniform": (37, lambda rng: rng.randint(0, 37, (4, 50))),
    "one_token": (37, lambda rng: np.full((3, 171), 5)),
    "zipf": (1000, lambda rng: _zipf_tokens(rng, (2, 333), 1000)),
    "first_and_last_ids": (64, lambda rng: rng.choice([0, 63], (5, 31))),
    "odd_count": (32, lambda rng: rng.randint(0, 32, (1, 127))),
    "vocab_not_power_of_two": (100, lambda rng: rng.randint(0, 100, (3, 64))),
}


@pytest.mark.parametrize("stream", list(EMBED_STREAMS))
def test_embed_bwd_matches_scatter_add_reference(stream):
    """The sorted, gather-only embedding backward equals a float64
    ``np.add.at`` to 1e-6 of the largest row, repeats bitwise, and lowers
    with no nondeterminism-prone primitive."""
    from repro.models import layers as L

    vocab, make = EMBED_STREAMS[stream]
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(make(rng), jnp.int32)
    dy = jnp.asarray(rng.randn(*tokens.shape, 8), jnp.float32)
    table = jnp.zeros((vocab, 8), jnp.float32)
    lookup = L._det_embed_lookup(vocab, "float32")

    def grad(tbl):
        return jax.vjp(lambda t: lookup(t, tokens), tbl)[1](dy)[0]

    got = np.asarray(jax.jit(grad)(table))
    np.testing.assert_array_equal(got, np.asarray(jax.jit(grad)(table)))
    ref = np.zeros((vocab, 8))
    np.add.at(ref, np.asarray(tokens).reshape(-1),
              np.asarray(dy, np.float64).reshape(-1, 8))
    assert np.max(np.abs(got - ref)) <= 1e-6 * np.max(np.abs(ref))
    assert trace.audit_fn(grad, table) == []


def test_embed_bwd_cost_has_no_vocab_sized_matmul():
    """At train-stablelm-4k's shape (8,192 tokens, 100,352 x 2048 table,
    bf16) the jitted backward stays O(T·d + V·d): a one-hot product costs
    3.37e12 FLOPs there, this path under 1e10. Compiled, never run."""
    from repro.models import layers as L

    cfg = registry.get("stablelm-1.6b")
    v, d = cfg.padded_vocab, cfg.d_model

    def loss(p, tokens, dy):
        emb = L.apply_embed(p, tokens, cfg)
        return jnp.sum(emb.astype(jnp.float32) * dy.astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss)).lower(
        {"tok": jax.ShapeDtypeStruct((v, d), jnp.float32)},
        jax.ShapeDtypeStruct((2, 4096), jnp.int32),
        jax.ShapeDtypeStruct((2, 4096, d), jnp.bfloat16)).compile()
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    assert v == 100_352 and cost["flops"] < 1e10, cost["flops"]


def test_embed_grad_paths_numerically_equal():
    """Both embedding backward realizations compute the same mathematical
    gradient (the deterministic one just pins the association)."""
    from repro.models import transformer as T
    cfg = registry.get("stablelm-1.6b").reduced()
    params = T.init(cfg, jax.random.PRNGKey(0))
    data = SyntheticLM(DataConfig(seed=0, batch=2, seq=16, vocab=cfg.vocab))
    batch = data.batch(0)

    def grad_with(c):
        return jax.grad(lambda p: T.loss_fn(p, batch, c)[0])(params)

    ga = grad_with(cfg)
    gb = grad_with(cfg.replace(det_embed_grad=False))
    for a, b in zip(jax.tree.leaves(ga), jax.tree.leaves(gb)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-5, atol=1e-5)
