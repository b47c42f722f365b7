"""The plain reference agrees with the program on seeded weights, at a tiny
size on the CPU, with both in float32 (bfloat16 storage is the cells'
business; here the equations are what is compared)."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.kinds import train
from bench.models import dense_decoder as D

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def tiny(name, **kw):
    with open(os.path.join(DATA, f"{name}.json")) as f:
        cfg = json.load(f)
    cfg.update(kw)
    cfg["name"] = name
    return cfg


def f32(cfg):
    cfg = dict(cfg, param_dtype="float32")
    cfg["program"] = dict(cfg["program"], overrides=dict(
        cfg["program"]["overrides"], dtype_name="float32"))
    return cfg


def gqa(cfg, kv_heads):
    """The same configuration with grouped-query attention."""
    cfg = dict(cfg, num_key_value_heads=kv_heads)
    cfg["program"] = dict(cfg["program"], overrides=dict(
        cfg["program"]["overrides"], n_kv_heads=kv_heads))
    return cfg


@pytest.mark.parametrize("kv_heads", [4, 2])
def test_forward_logits_match_program(kv_heads):
    from bench.adapters import dense_decoder as A
    from repro.models import transformer as T

    cfg = gqa(f32(tiny("tiny-stablelm")), kv_heads)
    mc = A.program_config(cfg)
    words = jnp.asarray(D.seed_words(2**31 + 5))
    neutral = D.make_all(cfg, words)
    params = A.to_program(mc, D.make_stacked(cfg, words))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 48), 0,
                                cfg["vocab_size"])
    got, _ = T.forward(params, {"tokens": tokens}, mc)
    want = []
    for row in tokens:
        x = jnp.take(neutral["global"]["embed"], row, axis=0)
        for p in neutral["layers"]:
            x = D.layer_forward(p, x, jnp.arange(row.shape[0]), cfg, q_block=16)
        want.append(D.logits_block(neutral["global"],
                                   D.final_norm(neutral["global"], x, cfg), cfg))
    want = jnp.stack(want)
    err = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
    assert err < 1e-5, err


def test_train_step_matches_program():
    from bench.adapters import dense_decoder as A

    cfg = f32(tiny("tiny-stablelm"))
    with open(os.path.join(DATA, "tiny-train.json")) as f:
        tr = json.load(f)
    mc = A.program_config(cfg)
    tcfg = A.train_config(tr)
    step = A.build_train_step(mc, tcfg)
    seed = 3
    words = jnp.asarray(D.seed_words(seed))
    state = A.init_state(A.to_program(mc, D.make_stacked(cfg, words)), tcfg)
    feed = train.batch_fn(cfg["vocab_size"], tr["batch"], tr["seq"],
                          D.root_key)
    losses = []
    for i in range(3):
        state, met = step(state, feed(words, i))
        losses.append(float(met["loss"]))
        if i == 0:
            grad = {k: float(v) for k, v in A.first_grad_norms(
                state, tcfg, cfg["num_hidden_layers"]).items()}
    ref = D.train_reference(cfg, tr["optimizer"], seed,
                            [(feed(words, i)["tokens"], feed(words, i)["labels"])
                             for i in range(3)])
    np.testing.assert_allclose(losses, ref["losses"], rtol=1e-5)
    p_end = A.leaves(state["params"], cfg["num_hidden_layers"])
    got = train.compare({"losses": losses, "grad": grad, "p_end": p_end},
                        ref, tr["leaf_rule"])
    assert got["grad_gap"] < 1e-3 and got["change_gap"] < 1e-3, got
