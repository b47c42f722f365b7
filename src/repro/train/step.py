"""Train / serve step builders with mesh shardings (pjit).

``make_train_state_fns(cfg, tcfg)`` returns (init_fn, step_fn, state_pspecs):
  state = {params, opt, ef?, step}; step_fn(state, batch) → (state, metrics).
Microbatch gradient accumulation (``lax.scan``) and remat are config-driven;
gradient clipping + optional int8 error-feedback compression precede the update.

Sharding: parameter PartitionSpecs come from the model's logical axes through the
active rule set (``dist/sharding.py``); optimizer state mirrors parameter specs;
batch is sharded over ``(pod, data)``. Everything is pure — the dry-run lowers
these exact step functions.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.dist import compression, fold
from repro.dist.sharding import logical_to_spec, spec_tree_to_pspecs
from repro.models import transformer as T
from repro.train import optimizer as O
from repro.verify import digest as V

F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    opt: O.OptConfig = O.OptConfig()
    microbatches: int = 1
    remat: bool = True
    remat_policy: str = "none"    # none (recompute all) | dots (save MXU outputs)
    grad_compression: Optional[str] = None    # None | "int8"
    seed: int = 0
    digest_metrics: bool = False  # ship a uint32 state fingerprint in metrics
                                  # (repro.verify.digest.tree_fingerprint) —
                                  # the live divergence alarm; sha256 chains
                                  # stay offline (verify.lifecycle)


def init_state(cfg: ModelConfig, tcfg: TrainConfig, key):
    params = T.init(cfg, key)
    state = {"params": params, "opt": O.opt_init(tcfg.opt, params),
             "step": jnp.zeros((), jnp.int32)}
    if tcfg.grad_compression:
        state["ef"] = compression.ef_init(params)
    return state


def state_pspecs(cfg: ModelConfig, tcfg: TrainConfig, rules):
    """PartitionSpec tree matching init_state's output."""
    pspecs = spec_tree_to_pspecs(T.specs(cfg), rules)
    opt_specs = (
        {"m": pspecs, "v": pspecs} if tcfg.opt.name == "adamw"
        else {"f": jax.tree.map(_factored_spec, pspecs,
                                is_leaf=lambda x: isinstance(x, P))})
    st = {"params": pspecs, "opt": opt_specs, "step": P()}
    if tcfg.grad_compression:
        st["ef"] = pspecs
    return st


def _factored_spec(spec: P):
    parts = tuple(spec)
    if len(parts) >= 2:
        return {"vr": P(*parts[:-1]), "vc": P(*(parts[:-2] + parts[-1:]))}
    return {"v": spec}


def batch_pspecs(cfg: ModelConfig, rules):
    bspec = logical_to_spec(("batch", None), rules)
    out = {"tokens": bspec, "labels": bspec}
    if cfg.packed_inputs:
        # packed-document batches (data.pipeline.pack_documents): per-token
        # segment ids + per-document restarting positions, sharded like tokens
        out["segment_ids"] = bspec
        out["positions"] = bspec
    b3 = logical_to_spec(("batch", None, None), rules)
    if cfg.frontend == "vision":
        out["vision_embeds"] = b3
    if cfg.encoder is not None:
        out["frames"] = b3
    return out


def jit(cfg: ModelConfig, fun, **kw):
    """Compile ``fun`` the way the trainer compiles its step for ``cfg``.

    In serve-canonical mode (``cfg.canonical_reductions``) the train forward
    is held bitwise to the serving engine's prefill, so it is compiled like
    the engine's steps, with ``fold.exact_jit``: every bf16 value is rounded
    where the program says, not where a fusion happens to. Otherwise plain
    ``jax.jit``.
    """
    if cfg.canonical_reductions:
        return fold.exact_jit(fun, **kw)
    return jax.jit(fun, **kw)


def make_train_step(cfg: ModelConfig, tcfg: TrainConfig):
    """Returns step(state, batch) → (state, metrics). Pure; jit outside
    (``jit`` above)."""

    def loss_fn(params, batch):
        return T.loss_fn(params, batch, cfg, remat=tcfg.remat,
                         remat_policy=tcfg.remat_policy)

    def grads_of(params, batch):
        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch)
        return loss, metrics, grads

    def step(state, batch):
        params = state["params"]
        if tcfg.microbatches > 1:
            mb = tcfg.microbatches

            def reshape(x):
                return x.reshape((mb, x.shape[0] // mb) + x.shape[1:])

            batches = jax.tree.map(reshape, batch)
            zero = jax.tree.map(lambda p: jnp.zeros(p.shape, F32), params)

            def acc_fn(carry, mb_batch):
                loss_a, grads_a = carry
                loss, metrics, grads = grads_of(params, mb_batch)
                grads_a = jax.tree.map(lambda a, g: a + g.astype(F32),
                                       grads_a, grads)
                return (loss_a + loss, grads_a), None

            (loss, grads), _ = jax.lax.scan(acc_fn, (jnp.zeros((), F32), zero),
                                            batches)
            loss = loss / mb
            grads = jax.tree.map(lambda g: g / mb, grads)
            metrics = {"ce": loss, "aux": jnp.zeros((), F32)}
        else:
            loss, metrics, grads = grads_of(params, batch)

        new_state = dict(state)
        if tcfg.grad_compression == "int8":
            grads, new_state["ef"] = compression.compress_grads(grads, state["ef"])
        new_p, new_opt, gnorm = O.opt_update(tcfg.opt, grads, state["opt"],
                                             params, state["step"])
        new_state.update(params=new_p, opt=new_opt, step=state["step"] + 1)
        metrics = dict(metrics, loss=loss, grad_norm=gnorm,
                       lr=O.lr_at(tcfg.opt, state["step"]))
        if tcfg.digest_metrics:
            metrics["state_fingerprint"] = V.tree_fingerprint(new_state)
        return new_state, metrics

    return step


def step_event(metrics: Dict[str, Any],
               keys: Tuple[str, ...] = ("loss", "grad_norm", "lr")
               ) -> Dict[str, float]:
    """Materialize one step's training metrics into a tracker payload.

    Host-side only (``repro.obs`` trackers never see traced values): pulling
    ``float()`` here is the single device sync, performed after the caller
    decided this step gets logged.  The uint32 ``state_fingerprint`` is
    deliberately excluded — it flows through
    :meth:`repro.obs.DivergenceAlarm.observe`, which owns the ``fingerprint``
    event and the divergence latch.
    """
    return {k: float(metrics[k]) for k in keys if k in metrics}


# --------------------------------------------------------------------- serve
def make_serve_step(cfg: ModelConfig):
    """decode step: (params, caches, batch, cache_pos[, cross_x]) → (logits, caches)."""

    def step(params, caches, batch, cache_pos, cross_x=None):
        return T.decode_step(params, caches, batch["tokens"], cache_pos, cfg,
                             cross_x=cross_x)

    return step


def make_prefill_step(cfg: ModelConfig, max_seq: Optional[int] = None):
    def step(params, batch):
        logits, caches, cross_x = T.prefill_step(params, batch, cfg,
                                                 max_seq=max_seq)
        return logits, caches
    return step


def cache_pspecs(cfg: ModelConfig, shape, rules, *, shard_seq: bool = False):
    """PartitionSpecs for the decode cache pytree (matches T.init_cache).

    shard_seq=True (long_500k, batch=1): KV-cache sequence axis sharded over
    (data, model) — sequence-parallel decode; otherwise batch over (pod, data)
    and heads over model where divisible."""
    batch_ax = logical_to_spec(("batch",), rules)[0]
    kv_ax = "model" if cfg.shard_kv else None
    out = {}
    n_rep = cfg.n_layers // len(cfg.block_pattern)
    for i, kind in enumerate(cfg.block_pattern):
        key = f"b{i}_{kind}"
        if kind.startswith("attn"):
            if shard_seq:
                kv = P(None, None, ("data", "model"), None, None)
            else:
                kv = P(None, batch_ax, None, kv_ax, None)
            out[key] = {"attn": (kv, kv)}
        elif kind.startswith("mamba"):
            mlp_ax = "model"
            out[key] = {"mamba": (P(None, batch_ax, None, mlp_ax),
                                  P(None, batch_ax, mlp_ax, None))}
        elif kind == "mlstm":
            h_ax = "model" if cfg.shard_heads else None
            out[key] = {"mlstm": (P(None, batch_ax, h_ax, None, None),
                                  P(None, batch_ax, h_ax, None),
                                  P(None, batch_ax, h_ax))}
        elif kind == "slstm":
            h_ax = "model" if cfg.shard_heads else None
            s3 = P(None, batch_ax, h_ax, None)
            out[key] = {"slstm": (s3, s3, s3, s3)}
    return out
