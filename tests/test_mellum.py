"""Mellum2-12B-A2.5B on the training path, at a tiny size on the CPU:
sliding-window and full layers (YaRN rope on the full ones) and a held-expert
MoE layer, against the benchmark's plain float32 reference
(``bench/models/window_moe_decoder.py``) on seeded weights."""
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.adapters import window_moe_decoder as A
from bench.models import window_moe_decoder as W
from repro.configs import registry
from repro.kernels import flash_bwd as FB
from repro.kernels import ops
from repro.masks.spec import SlidingWindow
from repro.models import layers as L
from repro.models import moe as MOE
from repro.models import transformer as T

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(ROOT, "bench", "configs", "mellum2-12b-a2.5b-4l-ep8.json")


def tiny(**kw):
    """The benchmark's configuration file at tiny widths, in float32: 16
    router experts, top 4, held experts 1, 4, 9 and 15, window 16."""
    with open(CONFIG) as f:
        cfg = json.load(f)
    cfg.update(name="tiny-mellum", hidden_size=64, num_attention_heads=4,
               num_key_value_heads=2, head_dim=16, moe_intermediate_size=32,
               vocab_size=256, router_experts=16, num_experts_per_tok=4,
               num_experts=4, held_experts=[1, 4, 9, 15], sliding_window=16,
               param_dtype="float32")
    cfg.update(kw)
    cfg["program"] = {"arch": "mellum2-12b-a2.5b", "overrides": {
        "d_model": 64, "n_heads": 4, "n_kv_heads": 2, "head_dim_": 16,
        "d_ff": 32, "n_experts": cfg["router_experts"],
        "top_k": cfg["num_experts_per_tok"], "vocab_pad": 128,
        "layer_windows": (cfg["sliding_window"],) * 3 + (0,),
        "dtype_name": cfg["param_dtype"]}}
    return cfg


def _batch(cfg, b=2, s=64, seed=1):
    ids = jax.random.randint(jax.random.PRNGKey(seed), (b, s + 1), 0,
                             cfg["vocab_size"])
    return ids[:, :-1], ids[:, 1:]


def test_program_matches_reference_loss_and_every_gradient():
    """At 1,024 tokens: two of the reference's query blocks, windows that
    reach across them."""
    cfg = tiny(sliding_window=96)
    mc = A.program_config(cfg)
    words = jnp.asarray(W.seed_words(2**33 + 7))
    tokens, labels = _batch(cfg, b=2, s=1024)
    with jax.default_matmul_precision("highest"):
        want, ref_g = jax.value_and_grad(W.batch_loss)(
            W.make_all(cfg, words), tokens, labels, cfg)
        params = A.to_program(mc, W.make_stacked(cfg, words))
        (got, _), g = jax.value_and_grad(
            lambda p: T.loss_fn(p, {"tokens": tokens, "labels": labels}, mc,
                                remat=True), has_aux=True)(params)
    np.testing.assert_allclose(float(got), float(want), rtol=2e-6)
    ref_g = W.flat(ref_g)
    got_g = A.leaves(g, cfg["num_hidden_layers"])
    assert set(got_g) == set(ref_g)
    for name, r in ref_g.items():
        # float32 on both sides: the two differ by the order of sums
        np.testing.assert_allclose(np.asarray(got_g[name]), np.asarray(r),
                                   rtol=2e-3, atol=2e-5 * float(
                                       jnp.max(jnp.abs(r))), err_msg=name)


@pytest.mark.parametrize("window", [0, 24, 64])
def test_reference_attention_blocks_match_one_block(window):
    """The reference's attention in query blocks (a loop over blocks, each
    over the keys it can see) equals the same attention in one block: the
    blocks land in order and the band loses no visible key."""
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (128, 4, 16), jnp.float32)
    k = jax.random.normal(ks[1], (128, 2, 16), jnp.float32)
    v = jax.random.normal(ks[2], (128, 2, 16), jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole = W._attention(q, k, v, {"hk": 2}, window, "f32", 128)
        blocks = W._attention(q, k, v, {"hk": 2}, window, "f32", 32)
    np.testing.assert_allclose(np.asarray(blocks), np.asarray(whole),
                               rtol=1e-5, atol=1e-6)


def _layer(n_experts=64, top_k=8, held=tuple(range(64)), router=None,
           seed=3):
    cfg = registry.get("mellum2-12b-a2.5b").replace(
        d_model=64, d_ff=32, n_experts=n_experts, top_k=top_k,
        moe_held=held, dtype_name="float32")
    p = dict(T.init(cfg.replace(n_layers=4), jax.random.PRNGKey(seed))
             ["blocks"]["b0_attn_moe"]["moe"])
    p = jax.tree.map(lambda a: a[0], p)
    if router is not None:
        p["router"] = router
    return cfg, p


def _shares_of(p, held):
    """A share's weights: the router whole, the held experts' rows."""
    idx = jnp.asarray(held)
    return dict(p, **{k: p[k][idx] for k in ("w_gate", "w_up", "w_down")})


def test_expert_shares_add_up_to_the_layer():
    """Eight chips each holding 8 of 64 experts: their parts add up to what
    the whole layer gives, and the balance loss is the layer's on each."""
    cfg, p = _layer()
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 48, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole, aux, _ = MOE.apply_moe_held(p, x, cfg)
        parts = []
        for c in range(8):
            held = tuple(range(8 * c, 8 * c + 8))
            y, a, r = MOE.apply_moe_held(_shares_of(p, held), x,
                                         cfg.replace(moe_held=held))
            parts.append(y)
            assert float(a) == pytest.approx(float(aux), rel=1e-6)
            assert int(r["dropped"]) == 0
            assert int(r["load"].sum()) + int(r["elsewhere"]) == 2 * 48 * 8
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(whole),
                               rtol=1e-5, atol=1e-6)
    # and the whole layer is the reference's, every expert held
    m = {"e": 64, "k": 8, "held": tuple(range(64)), "renorm": True}
    want = jnp.stack([W._experts(p, row, m, "f32")[0] for row in x])
    np.testing.assert_allclose(np.asarray(whole), np.asarray(want),
                               rtol=1e-4, atol=1e-6)


def test_no_slot_dropped_under_a_skewed_router():
    """A router that sends every token to experts 0-7 (all logits equal:
    ties go to the lowest ids) fills each held expert with every token; the
    grouped rows hold them all, where a capacity factor of 1.25 drops most."""
    cfg, p = _layer(held=tuple(range(8)),
                    router=jnp.zeros((64, 64), jnp.float32))
    p = _shares_of(p, tuple(range(8)))
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 32, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        y, _, r = MOE.apply_moe_held(p, x, cfg)
        m = {"e": 64, "k": 8, "held": tuple(range(8)), "renorm": True}
        want = jnp.stack([W._experts(p, row, m, "f32")[0] for row in x])
    assert np.asarray(r["load"]).tolist() == [64] * 8
    assert int(r["dropped"]) == 0 and int(r["elsewhere"]) == 0
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=1e-5,
                               atol=1e-6)


def _hf_yarn(dim, theta, factor, orig, beta_fast, beta_slow):
    """HF transformers' YaRN inverse frequencies, in float64."""
    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(theta))
    low = max(math.floor(corr(beta_fast)), 0)
    high = min(math.ceil(corr(beta_slow)), dim - 1)
    pos_freqs = theta ** (np.arange(0, dim, 2) / dim)
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    extra = 1 - ramp
    return (1 / (factor * pos_freqs)) * (1 - extra) + (1 / pos_freqs) * extra


def test_yarn_cos_sin_match_the_formula():
    cfg = registry.get("mellum2-12b-a2.5b")
    y = cfg.rope_yarn
    inv = _hf_yarn(128, 500_000.0, 16.0, 8192, 32.0, 1.0)
    np.testing.assert_allclose(L.yarn_inv_freq(128, cfg.rope_theta, y), inv,
                               rtol=1e-6)
    assert y.attention_factor == pytest.approx(0.1 * math.log(16) + 1)
    # the full layer rotates by YaRN, the sliding layers by the default rule
    full, sliding = T.position_config(cfg, 3), T.position_config(cfg, 0)
    assert full.rope_yarn == y and full.attn_window == 0
    assert sliding.rope_yarn is None and sliding.attn_window == 1024
    pos = np.array([0, 1, 7, 100, 1000, 8191])
    x = jax.random.normal(jax.random.PRNGKey(0), (6, 1, 128), jnp.float32)
    got = L.rope(x[None], jnp.asarray(pos)[None], 500_000.0, 1.0, y)[0]
    ang = pos[:, None] * inv[None, :]
    cos, sin = (np.cos(ang) * y.attention_factor,
                np.sin(ang) * y.attention_factor)
    x1, x2 = np.asarray(x[:, 0, :64]), np.asarray(x[:, 0, 64:])
    want = np.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    # float32 angles: pos · inv_freq rounds to 6e-8 of itself, 5e-4 rad at
    # position 8191
    np.testing.assert_allclose(np.asarray(got[:, 0]), want, rtol=3e-3,
                               atol=3e-3)


@pytest.mark.parametrize("s,window,block,worker_parallel", [
    pytest.param(512, 160, None, False, id="False"),
    pytest.param(512, 160, None, True, id="True"),
    pytest.param(1024, 384, 256, False, id="256-False"),
    pytest.param(1024, 384, 256, True, id="256-True"),
    pytest.param(1024, 384, 512, False, id="512-False"),
    pytest.param(1024, 384, 512, True, id="512-True"),
])
def test_window_backward_matches_dense_oracle(s, window, block,
                                              worker_parallel):
    """The block-sparse DASH backward under SlidingWindow, GQA 4/2 at head
    dim 128, in both realizations, against the dense masked reference: at
    the default tile and at 256 and 512 tiles, where a window of 384 leaves
    PARTIAL tiles at both edges of the band."""
    ks = jax.random.split(jax.random.PRNGKey(6), 4)
    q = jax.random.normal(ks[0], (1, 4, s, 128), jnp.float32)
    k = jax.random.normal(ks[1], (1, 2, s, 128), jnp.float32)
    v = jax.random.normal(ks[2], (1, 2, s, 128), jnp.float32)
    do = jax.random.normal(ks[3], (1, 4, s, 128), jnp.float32)
    mask = SlidingWindow(window)

    def grads(f):
        return jax.vjp(f, q, k, v)[1](do)

    got = grads(lambda a, b, c: ops.dash_attention(
        a, b, c, mask=mask, interpret=True, block=block,
        worker_parallel=worker_parallel))
    rec = ops.last_tiling()
    assert rec["realization"] == (
        "worker_parallel" if worker_parallel else "serialized")
    assert block is None or rec["block"] == block
    with jax.default_matmul_precision("highest"):
        want = grads(lambda a, b, c: ops.xla_attention(a, b, c, mask=mask))
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-4,
                                   atol=2e-4)


def test_window_backward_takes_the_serialized_realization_at_8k():
    """At S 8192, d 128, 2 × 32 heads, the windowed Shift schedule's 64
    workers would keep 17.2 GB of dQ partials: the serialized realization is
    taken. The causal schedule at 512-square tiles keeps its 16 workers
    (4 GiB of partials). The limit lies a factor of two from both."""
    from repro.core.schedules import cached_schedule

    one = FB.dq_bytes(64, 8192, 128)
    win = cached_schedule("shift", 64, n_heads=1, causal=False,
                          mask=SlidingWindow(1024), block_q=128, block_k=128)
    assert FB.worker_grid(win, True, 0)["valid"].shape[0] == 64
    assert 64 * one > 17e9 and FB.worker_grid(win, True, one) is None
    assert 64 * one >= 2 * FB.DQ_PARTIALS_MAX_BYTES
    causal = cached_schedule("symmetric_shift", 16, n_heads=1, causal=True)
    wc = FB.worker_grid(causal, True, one)
    assert wc["valid"].shape[0] * one == 4 * 2**30
    assert 2 * wc["valid"].shape[0] * one <= FB.DQ_PARTIALS_MAX_BYTES


def test_train_step_bitwise_repeatable():
    """The reduced Mellum2 train step, run twice from one state on one
    batch, gives the same bits: weights, optimizer state and loss."""
    from repro.launch.train import build
    from repro.train import step as S

    cfg = registry.get("mellum2-12b-a2.5b").reduced(
        layer_windows=(16, 16, 16, 0))
    tcfg = S.TrainConfig()
    tokens, labels = _batch({"vocab_size": cfg.vocab}, s=64)
    batch = {"tokens": tokens, "labels": labels}
    step = build(cfg, tcfg)
    outs = []
    for _ in range(2):
        state = S.init_state(cfg, tcfg, jax.random.PRNGKey(0))
        state, met = step(state, batch)
        outs.append(jax.device_get((state, met["loss"],
                                    met["moe_routing"])))
    for a, b in zip(jax.tree.leaves(outs[0]), jax.tree.leaves(outs[1])):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
    ev = MOE.routing_event(outs[0][2])
    assert ev["dropped"] == 0 and len(ev["load"]) == 4
