"""The benchmark's interface to the program for dense decoder configurations.

Everything the harness takes from the system under test goes through here:
its model configuration (``repro.configs.registry``), its train step
(``repro.launch.train.build``) and its state layout. The weights, data,
traffic and the reference that decides ``correct`` are the benchmark's own.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32

# neutral leaf name -> path in the program's parameter tree ("@" marks the
# stacked per-layer blocks of the single attention block kind)
_PATHS = {
    "embed": ("embed", "tok"), "head": ("lm_head", "w"),
    "lnf_scale": ("ln_f", "scale"), "lnf_bias": ("ln_f", "bias"),
    "ln1_scale": ("@", "ln1", "scale"), "ln1_bias": ("@", "ln1", "bias"),
    "wq": ("@", "attn", "wq"), "bq": ("@", "attn", "bq"),
    "wk": ("@", "attn", "wk"), "bk": ("@", "attn", "bk"),
    "wv": ("@", "attn", "wv"), "bv": ("@", "attn", "bv"),
    "wo": ("@", "attn", "wo"),
    "ln2_scale": ("@", "ln2", "scale"), "ln2_bias": ("@", "ln2", "bias"),
    "w_gate": ("@", "mlp", "w_gate"), "w_up": ("@", "mlp", "w_up"),
    "w_down": ("@", "mlp", "w_down"),
}
_BLOCK = "b0_attn"


def program_config(cfg: dict):
    """The program's ``ModelConfig`` for a configuration file, checked
    against every size the file states: a program that departs from the
    configuration is refused, not measured."""
    from repro.configs import registry

    prog = cfg["program"]
    mc = registry.get(prog["arch"]).replace(
        n_layers=cfg["num_hidden_layers"], **prog.get("overrides", {}))
    hd = cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]
    want = {
        "d_model": cfg["hidden_size"], "n_heads": cfg["num_attention_heads"],
        "n_kv_heads": cfg["num_key_value_heads"], "head_dim": hd,
        "d_ff": cfg["intermediate_size"], "vocab": cfg["vocab_size"],
        "padded_vocab": cfg["vocab_size"],
        "norm": cfg["norm"], "activation": "silu",
        "qkv_bias": bool(cfg.get("use_qkv_bias", False)),
        "rope_theta": float(cfg["rope_theta"]),
        "rope_pct": float(cfg.get("partial_rotary_factor", 1.0)),
        "tie_embeddings": bool(cfg["tie_word_embeddings"]),
        "pos_embed": "rope", "block_pattern": ("attn",),
        "dtype_name": cfg["param_dtype"],
    }
    bad = {k: (getattr(mc, k), v) for k, v in want.items()
           if getattr(mc, k) != v}
    if bad:
        raise ValueError(f"program config departs from {cfg['name']}: "
                         f"{bad} (program, configuration)")
    return mc


def _get(tree, path):
    node = tree["blocks"][_BLOCK] if path[0] == "@" else tree
    for k in path[1:] if path[0] == "@" else path:
        node = node[k]
    return node


def _set(tree, path, value):
    node = tree
    keys = (("blocks", _BLOCK) + path[1:]) if path[0] == "@" else path
    for k in keys[:-1]:
        node = node.setdefault(k, {})
    node[keys[-1]] = value


def to_program(mc, stacked: dict) -> dict:
    """Weights as ``models.dense_decoder.make_stacked`` makes them -> the
    program's parameter tree, each leaf in the program's own type, which
    must equal the configuration's storage type."""
    from repro.models import transformer as T

    shapes = jax.eval_shape(lambda: T.init(mc, jax.random.PRNGKey(0)))
    tree: dict = {}
    for name, path in _PATHS.items():
        group = stacked["layers"] if path[0] == "@" else stacked["global"]
        if name not in group:
            continue
        val = group[name]
        want = _get(shapes, path)
        if want.shape != val.shape or want.dtype != val.dtype:
            raise ValueError(f"{name}: program leaf {want.shape} {want.dtype}"
                             f", benchmark {val.shape} {val.dtype}")
        _set(tree, path, val)
    if jax.tree.structure(tree) != jax.tree.structure(shapes):
        raise ValueError("benchmark weights do not cover the program's "
                         "parameter tree")
    return tree


def leaves(tree: dict, n_layers: int) -> dict:
    """The leaves of a program-layout tree under the neutral names
    ``L<i>.<leaf>`` / ``<leaf>`` of ``models.dense_decoder``, one layer of a
    stacked leaf each. Traceable."""
    out = {}
    for name, path in _PATHS.items():
        try:
            leaf = _get(tree, path)
        except KeyError:
            continue
        if path[0] == "@":
            for i in range(n_layers):
                out[f"L{i}.{name}"] = leaf[i]
        else:
            out[name] = leaf
    return out


def leaf_norms(tree: dict, n_layers: int) -> dict:
    """Per-(layer, leaf) norms of a program-layout tree, under the neutral
    names. Traceable."""
    return {k: jnp.sqrt(jnp.sum(jnp.square(x.astype(F32))))
            for k, x in leaves(tree, n_layers).items()}


# --------------------------------------------------------------------------- #
# training
# --------------------------------------------------------------------------- #
def train_config(traffic: dict):
    from repro.train import optimizer as O
    from repro.train import step as S

    o = traffic["optimizer"]
    opt = O.OptConfig(name="adamw", lr=o["lr"], warmup_steps=1,
                      schedule="constant", b1=o["b1"], b2=o["b2"],
                      eps=o["eps"], weight_decay=o["weight_decay"],
                      clip_norm=o["clip_norm"], state_dtype="float32")
    return S.TrainConfig(opt=opt, remat=traffic["remat"])


def build_train_step(mc, tcfg):
    """The trainer's own compiled step (``launch.train.build``: donated
    state)."""
    from repro.launch.train import build
    return build(mc, tcfg)


def init_state(params, tcfg):
    from repro.train import optimizer as O
    return {"params": params, "opt": O.opt_init(tcfg.opt, params),
            "step": jnp.zeros((), jnp.int32)}


def first_grad_norms(state, tcfg, n_layers: int):
    """Per-leaf norms of the first gradient as the optimizer got it, from
    the state after one step: m = (1 - b1)·g when m starts at zero."""
    return leaf_norms(jax.tree.map(lambda m: m / (1.0 - tcfg.opt.b1),
                                   state["opt"]["m"]), n_layers)


def params_of(state):
    return state["params"]
