"""The benchmark's interface to the program for decoders of sliding-window
and full attention layers with held-expert MoE layers (``models/
window_moe_decoder.py``).

Everything the harness takes from the system under test goes through here:
its model configuration (``repro.configs.registry``), its train step
(``repro.launch.train.build``) and its state layout. Training goes through
``adapters/dense_decoder.py``'s functions, which read the state by the leaf
names this module's ``leaves`` gives.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.adapters.dense_decoder import (build_train_step, init_state,
                                          params_of, train_config)

F32 = jnp.float32
__all__ = ["program_config", "to_program", "leaves", "first_grad_norms",
           "build_train_step", "init_state", "params_of", "train_config"]

# neutral leaf name -> path in a block of the program's parameter tree
_GLOBAL = {"embed": ("embed", "tok"), "head": ("lm_head", "w"),
           "lnf_scale": ("ln_f", "scale")}
_LAYER = {"ln1_scale": ("ln1", "scale"), "wq": ("attn", "wq"),
          "wk": ("attn", "wk"), "wv": ("attn", "wv"), "wo": ("attn", "wo"),
          "ln2_scale": ("ln2", "scale"), "router": ("moe", "router"),
          "w_gate": ("moe", "w_gate"), "w_up": ("moe", "w_up"),
          "w_down": ("moe", "w_down")}


def program_config(cfg: dict):
    """The program's ``ModelConfig`` for a configuration file, this chip's
    share (layers, held experts, vocabulary slice) taken from the file, and
    checked against every size the file states: a program that departs
    from the configuration is refused, not measured."""
    from repro.configs import registry
    from repro.configs.base import Yarn

    prog = cfg["program"]
    mc = registry.get(prog["arch"]).replace(
        n_layers=cfg["num_hidden_layers"], vocab=cfg["vocab_size"],
        moe_held=tuple(cfg["held_experts"]), **prog.get("overrides", {}))
    types = cfg["layer_types"][:len(mc.block_pattern)]
    full = cfg["rope_parameters"]["full_attention"]
    sliding = cfg["rope_parameters"]["sliding_attention"]
    want = {
        "d_model": cfg["hidden_size"], "n_heads": cfg["num_attention_heads"],
        "n_kv_heads": cfg["num_key_value_heads"], "head_dim": cfg["head_dim"],
        "d_ff": cfg["moe_intermediate_size"], "vocab": cfg["vocab_size"],
        "padded_vocab": cfg["vocab_size"], "norm": "rmsnorm",
        "norm_eps": cfg["rms_norm_eps"], "activation": cfg["hidden_act"],
        "qkv_bias": cfg["attention_bias"],
        "tie_embeddings": cfg["tie_word_embeddings"], "pos_embed": "rope",
        "rope_pct": 1.0,
        "block_pattern": ("attn_moe",) * len(types),
        "layer_windows": tuple(cfg["sliding_window"]
                               if t == "sliding_attention" else 0
                               for t in types),
        "rope_theta": float(sliding["rope_theta"]),
        "rope_yarn": Yarn(
            factor=float(full["factor"]),
            original_max=full["original_max_position_embeddings"],
            beta_fast=float(full["beta_fast"]),
            beta_slow=float(full["beta_slow"]),
            attention_factor=float(full["attention_factor"])),
        "n_experts": cfg["router_experts"],
        "top_k": cfg["num_experts_per_tok"],
        "renorm_topk": cfg["norm_topk_prob"],
        "moe_held": tuple(cfg["held_experts"]), "n_shared_experts": 0,
        "moe_aux_weight": cfg["router_aux_loss_coef"],
        "dtype_name": cfg["param_dtype"],
    }
    bad = {k: (getattr(mc, k), v) for k, v in want.items()
           if getattr(mc, k) != v}
    if (sliding["rope_type"], full["rope_type"]) != ("default", "yarn") or (
            float(full["rope_theta"]) != mc.rope_theta):
        bad["rope_parameters"] = (mc.rope_theta, cfg["rope_parameters"])
    if cfg["num_hidden_layers"] % len(types) or len(cfg["held_experts"]) != (
            cfg["num_experts"]):
        bad["layers or experts"] = (cfg["num_hidden_layers"],
                                    cfg["num_experts"])
    if bad:
        raise ValueError(f"program config departs from {cfg['name']}: "
                         f"{bad} (program, configuration)")
    return mc


def _blocks(n_pattern: int):
    return [f"b{i}_attn_moe" for i in range(n_pattern)]


def to_program(mc, stacked: dict) -> dict:
    """Weights as ``models.window_moe_decoder.make_stacked`` makes them ->
    the program's parameter tree: layer r·P + i is repeat r of pattern
    position i. Each leaf keeps its type, which must be the program's."""
    from repro.models import transformer as T

    shapes = jax.eval_shape(lambda: T.init(mc, jax.random.PRNGKey(0)))
    g, lay = stacked["global"], stacked["layers"]
    n_pat = len(mc.block_pattern)
    tree = {top: {leaf: g[name]} for name, (top, leaf) in _GLOBAL.items()}
    tree["blocks"] = {}
    for i, key in enumerate(_blocks(n_pat)):
        blk: dict = {}
        for name, (grp, leaf) in _LAYER.items():
            blk.setdefault(grp, {})[leaf] = lay[name][i::n_pat]
        tree["blocks"][key] = blk
    if jax.tree.structure(tree) != jax.tree.structure(shapes):
        raise ValueError("benchmark weights do not cover the program's "
                         "parameter tree")
    for (path, want), got in zip(jax.tree_util.tree_leaves_with_path(shapes),
                                 jax.tree.leaves(tree)):
        if want.shape != got.shape or want.dtype != got.dtype:
            raise ValueError(f"{jax.tree_util.keystr(path)}: program leaf "
                             f"{want.shape} {want.dtype}, benchmark "
                             f"{got.shape} {got.dtype}")
    return tree


def leaves(tree: dict, n_layers: int) -> dict:
    """The leaves of a program-layout tree under the neutral names
    ``L<i>.<leaf>`` / ``<leaf>`` of the reference. Traceable."""
    out = {name: tree[top][leaf] for name, (top, leaf) in _GLOBAL.items()}
    blocks = _blocks(len(tree["blocks"]))
    for layer in range(n_layers):
        r, i = divmod(layer, len(blocks))
        blk = tree["blocks"][blocks[i]]
        for name, (grp, leaf) in _LAYER.items():
            out[f"L{layer}.{name}"] = blk[grp][leaf][r]
    return out


def first_grad_norms(state, tcfg, n_layers: int):
    """Per-leaf norms of the first gradient as the optimizer got it, from
    the state after one step: m = (1 - b1)·g when m starts at zero."""
    m = jax.tree.map(lambda a: a / (1.0 - tcfg.opt.b1), state["opt"]["m"])
    return {k: jnp.sqrt(jnp.sum(jnp.square(x.astype(F32))))
            for k, x in leaves(m, n_layers).items()}
