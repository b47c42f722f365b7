"""Plain reference of a dense decoder-only transformer, and its weights.

This is the yardstick's own model: the published equations in float32 at
``highest`` matmul precision, written from the model cards and never from
the program under test. It imports nothing of ``repro``.

Covers the dense decoders of the benchmark's configurations, from their
configuration files: LayerNorm with bias or RMSNorm, optional q/k/v biases,
rotary on a leading share of each head (HF rotate-half), grouped-query
attention, a SwiGLU MLP and an untied head; StableLM-2
(hf:stabilityai/stablelm-2-1_6b) is the first.

Departures from the published models, all deliberate:
  * weights are random and made from the seed (``weights``), every value
    exactly representable in bfloat16;
  * the reference keeps each parameter in the storage type the configuration
    states (``param_dtype`` for matrices and biases, ``norm_dtype`` for norm
    parameters) and rounds to it after an optimizer update, as the
    configuration's training run does; all arithmetic is float32;
  * attention is computed in query blocks and layers under ``jax.checkpoint``
    so that it fits one chip; that changes memory, not the mathematics.

``precision="fp8"`` is the control: every matrix product, forward and
backward, takes its operands rounded to float8 e4m3 with one scale per
tensor, as an fp8 training path would.
"""
from __future__ import annotations

import functools
import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0

# Weight tags: one key per leaf, fixed by (tag, layer, name) and never by
# traversal order.
_TAG_WEIGHTS = 0x5EED
_GLOBAL_LEAVES = ("embed", "head", "lnf_scale", "lnf_bias")
_LAYER_LEAVES = ("ln1_scale", "ln1_bias", "wq", "bq", "wk", "bk", "wv", "bv",
                 "wo", "ln2_scale", "ln2_bias", "w_gate", "w_up", "w_down")


# --------------------------------------------------------------------------- #
# shapes
# --------------------------------------------------------------------------- #
def dims(cfg: dict) -> dict:
    """The sizes the equations need, from a configuration file's keys."""
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    hd = cfg.get("head_dim") or d // h
    return dict(d=d, h=h, hk=cfg["num_key_value_heads"], hd=hd,
                f=cfg["intermediate_size"], v=cfg["vocab_size"],
                layers=cfg["num_hidden_layers"],
                layernorm=cfg["norm"] == "layernorm",
                qkv_bias=bool(cfg.get("use_qkv_bias", False)),
                eps=cfg.get("layer_norm_eps", cfg.get("rms_norm_eps", 1e-5)),
                theta=float(cfg["rope_theta"]),
                rot=int(hd * cfg.get("partial_rotary_factor", 1.0)))


def layer_shapes(cfg: dict) -> Dict[str, tuple]:
    m = dims(cfg)
    d, h, hk, hd, f = m["d"], m["h"], m["hk"], m["hd"], m["f"]
    s = {"ln1_scale": (d,), "wq": (d, h * hd), "wk": (d, hk * hd),
         "wv": (d, hk * hd), "wo": (h * hd, d), "ln2_scale": (d,),
         "w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}
    if m["layernorm"]:
        s.update(ln1_bias=(d,), ln2_bias=(d,))
    if m["qkv_bias"]:
        s.update(bq=(h * hd,), bk=(hk * hd,), bv=(hk * hd,))
    return s


def global_shapes(cfg: dict) -> Dict[str, tuple]:
    m = dims(cfg)
    s = {"embed": (m["v"], m["d"]), "head": (m["d"], m["v"]),
         "lnf_scale": (m["d"],)}
    if m["layernorm"]:
        s["lnf_bias"] = (m["d"],)
    return s


def is_norm(name: str) -> bool:
    return name.startswith("ln")


def storage_dtype(cfg: dict, name: str):
    return jnp.dtype(cfg["norm_dtype"] if is_norm(name) else cfg["param_dtype"])


# --------------------------------------------------------------------------- #
# weights from the seed
# --------------------------------------------------------------------------- #
def seed_words(seed: int) -> np.ndarray:
    """Any non-negative whole number (more than 32 bits too) as two uint32
    words. Programs take the words as an argument, never as a constant, so
    one compiled program serves every seed."""
    return np.asarray([seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF],
                      np.uint32)


def root_key(words, tag: int):
    """The key of stream ``tag`` of a seed, from its ``seed_words``."""
    k = jax.random.fold_in(jax.random.PRNGKey(words[0]), words[1])
    return jax.random.fold_in(k, tag)


def _leaf(key, name: str, shape, std: float):
    """One leaf: uniform with standard deviation ``std`` (centred at 1 for
    norm scales), rounded to bfloat16 so that every storage type holds the
    same value exactly."""
    a = std * math.sqrt(3.0)
    u = jax.random.uniform(key, shape, F32, -a, a)
    if name.endswith("_scale"):
        u = 1.0 + u
    return u.astype(jnp.bfloat16)


def _make(cfg: dict, words, layer, shapes, names) -> Dict[str, jax.Array]:
    k = jax.random.fold_in(root_key(words, _TAG_WEIGHTS), layer)
    std = cfg["initializer_range"]
    return {n: _leaf(jax.random.fold_in(k, names.index(n)), n, s, std)
            .astype(storage_dtype(cfg, n)) for n, s in shapes.items()}


def make_global(cfg: dict, words) -> Dict[str, jax.Array]:
    """Embedding, head and final norm, each in its storage type."""
    return _make(cfg, words, 0, global_shapes(cfg), _GLOBAL_LEAVES)


def make_layer(cfg: dict, words, layer) -> Dict[str, jax.Array]:
    """Layer ``layer``'s leaves (``layer`` may be traced)."""
    return _make(cfg, words, layer + 1, layer_shapes(cfg), _LAYER_LEAVES)


def make_all(cfg: dict, words) -> dict:
    """Every leaf: ``{"global": {...}, "layers": [{...}, ...]}``. Call it
    under ``jax.jit``: the weights are then made on the device in one call."""
    return {"global": make_global(cfg, words),
            "layers": [make_layer(cfg, words, i)
                       for i in range(cfg["num_hidden_layers"])]}


def make_stacked(cfg: dict, words) -> dict:
    """The same leaves with the layers stacked on a leading axis,
    ``{"global": {...}, "layers": {name: (layers, ...)}}``, made in place
    (no per-layer copies to stack), for a program that scans its layers."""
    layers = jnp.arange(cfg["num_hidden_layers"], dtype=jnp.int32)
    return {"global": make_global(cfg, words),
            "layers": jax.vmap(lambda i: make_layer(cfg, words, i))(layers)}


# --------------------------------------------------------------------------- #
# matrix products in the stated precision
# --------------------------------------------------------------------------- #
def _q8(x):
    """Round to float8 e4m3 with one scale per tensor, back to float32."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return (x / scale).astype(FP8).astype(F32) * scale


@jax.custom_vjp
def _mm_fp8(a, b):
    return jnp.matmul(_q8(a), _q8(b), precision="highest")


def _mm_fp8_fwd(a, b):
    return _mm_fp8(a, b), (a, b)


def _mm_fp8_bwd(res, g):
    a, b = res
    qa, qb, qg = _q8(a), _q8(b), _q8(g)
    da = jnp.matmul(qg, jnp.swapaxes(qb, -1, -2), precision="highest")
    db = jnp.matmul(jnp.swapaxes(qa, -1, -2), qg, precision="highest")
    # a broadcast batch axis of b (weights shared across rows) sums out
    while db.ndim > b.ndim:
        db = db.sum(0)
    return da, db


_mm_fp8.defvjp(_mm_fp8_fwd, _mm_fp8_bwd)


def matmul(a, b, precision: str):
    if precision == "fp8":
        return _mm_fp8(a, b)
    return jnp.matmul(a, b, precision="highest")


# --------------------------------------------------------------------------- #
# the equations
# --------------------------------------------------------------------------- #
def _norm(x, scale, bias, m):
    if m["layernorm"]:
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + m["eps"]) * scale + bias
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + m["eps"]) * scale


def _rope(x, pos, m):
    """HF rotate-half rotary embedding on the first ``rot`` dims of a head.
    x: (S, H, hd); pos: (S,)."""
    rot = m["rot"]
    if rot == 0:
        return x
    half = rot // 2
    inv = m["theta"] ** (-jnp.arange(0, rot, 2, dtype=F32) / rot)   # (half,)
    ang = pos.astype(F32)[:, None] * inv[None, :]                    # (S, half)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    xr, xp = x[..., :rot], x[..., rot:]
    rotated = jnp.concatenate([-xr[..., half:], xr[..., :half]], -1)
    return jnp.concatenate([xr * cos + rotated * sin, xp], -1)


def _attention(q, k, v, m, precision, q_block):
    """Causal attention; q (S, H, hd), k/v (S, Hk, hd) -> (S, H*hd).
    Key heads are shared by groups of H/Hk query heads, as published."""
    s, h, hd = q.shape
    g = h // m["hk"]
    kh = jnp.repeat(k, g, axis=1).transpose(1, 2, 0)     # (H, hd, S)
    vh = jnp.repeat(v, g, axis=1).transpose(1, 0, 2)     # (H, S, hd)
    qh = q.transpose(1, 0, 2) / math.sqrt(hd)            # (H, S, hd)
    kpos = jnp.arange(s)

    @jax.checkpoint
    def block(qb, start):
        sc = matmul(qb, kh, precision)                   # (H, qb, S)
        qpos = start + jnp.arange(qb.shape[1])
        sc = jnp.where(qpos[:, None] >= kpos[None, :], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return matmul(p, vh, precision)                  # (H, qb, hd)

    nb = s // q_block
    outs = [block(qh[:, i * q_block:(i + 1) * q_block], i * q_block)
            for i in range(nb)]
    out = jnp.concatenate(outs, axis=1)                  # (H, S, hd)
    return out.transpose(1, 0, 2).reshape(s, h * hd)


def layer_forward(p, x, pos, cfg, precision="f32", q_block=512):
    """One decoder layer on one sequence: x (S, D) float32."""
    m = dims(cfg)
    f = {n: a.astype(F32) for n, a in p.items()}
    h = _norm(x, f["ln1_scale"], f.get("ln1_bias"), m)
    q = matmul(h, f["wq"], precision)
    k = matmul(h, f["wk"], precision)
    v = matmul(h, f["wv"], precision)
    if m["qkv_bias"]:
        q, k, v = q + f["bq"], k + f["bk"], v + f["bv"]
    s = x.shape[0]
    q = _rope(q.reshape(s, m["h"], m["hd"]), pos, m)
    k = _rope(k.reshape(s, m["hk"], m["hd"]), pos, m)
    v = v.reshape(s, m["hk"], m["hd"])
    a = _attention(q, k, v, m, precision, min(q_block, s))
    x = x + matmul(a, f["wo"], precision)
    h = _norm(x, f["ln2_scale"], f.get("ln2_bias"), m)
    gate = matmul(h, f["w_gate"], precision)
    up = matmul(h, f["w_up"], precision)
    return x + matmul(jax.nn.silu(gate) * up, f["w_down"], precision)


def final_norm(g, x, cfg):
    m = dims(cfg)
    return _norm(x, g["lnf_scale"].astype(F32),
                 None if g.get("lnf_bias") is None
                 else g["lnf_bias"].astype(F32), m)


def logits_block(g, hn, cfg, precision="f32"):
    """Final-normed rows (n, D) -> logits (n, V) float32."""
    return matmul(hn, g["head"].astype(F32), precision)


# --------------------------------------------------------------------------- #
# training: loss, gradients and AdamW
# --------------------------------------------------------------------------- #
def sequence_loss_sum(params, tokens, labels, cfg, precision="f32",
                      row_block=1024):
    """Summed next-token cross-entropy of one sequence (S,) -> scalar."""
    g, layers = params["global"], params["layers"]
    x = jnp.take(g["embed"].astype(F32), tokens, axis=0)
    pos = jnp.arange(tokens.shape[0])
    for p in layers:
        x = jax.checkpoint(functools.partial(
            layer_forward, cfg=cfg, precision=precision))(p, x, pos)
    hn = final_norm(g, x, cfg)

    @jax.checkpoint
    def ce(hb, lb):
        lg = logits_block(g, hb, cfg, precision)
        lse = jax.nn.logsumexp(lg, axis=-1)
        gold = jnp.take_along_axis(lg, lb[:, None], axis=-1)[:, 0]
        return jnp.sum(lse - gold)

    n = tokens.shape[0]
    blk = min(row_block, n)
    return sum(ce(hn[i:i + blk], labels[i:i + blk]) for i in range(0, n, blk))


def flat(tree) -> Dict[str, jax.Array]:
    """The leaves (per layer), named ``L<i>.<leaf>`` or ``<leaf>``."""
    out = dict(tree["global"])
    for i, lay in enumerate(tree["layers"]):
        out.update({f"L{i}.{n}": a for n, a in lay.items()})
    return out


def _norms(tree) -> Dict[str, jax.Array]:
    """Norms per leaf (per layer), named as ``flat`` names them."""
    return {n: jnp.sqrt(jnp.sum(jnp.square(a.astype(F32))))
            for n, a in flat(tree).items()}


def _step_fn(cfg, opt, precision):
    """One training step of the reference: the loss is the mean over every
    token of the batch, as published for causal LM training; then AdamW
    (Loshchilov & Hutter) after clipping by the global norm, with bias
    correction, decoupled weight decay and float32 moments; parameters are
    rounded back to their storage type. Rows are summed one at a time under
    ``jax.checkpoint`` so that one row's activations are live at once."""
    b1, b2, eps = opt["b1"], opt["b2"], opt["eps"]
    lr, wd, clip = opt["lr"], opt["weight_decay"], opt["clip_norm"]

    def batch_loss(params, tokens, labels):
        row = jax.checkpoint(functools.partial(
            sequence_loss_sum, cfg=cfg, precision=precision))

        def body(acc, tl):
            return acc + row(params, *tl), None

        total, _ = jax.lax.scan(body, jnp.zeros((), F32), (tokens, labels))
        return total / tokens.size

    def step(params, m, v, tokens, labels, t):
        loss, grads = jax.value_and_grad(batch_loss)(params, tokens, labels)
        grads = jax.tree.map(lambda x: x.astype(F32), grads)
        gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(grads)))
        scale = jnp.minimum(1.0, clip / jnp.maximum(gnorm, 1e-9))
        raw = _norms(grads)
        grads = jax.tree.map(lambda x: x * scale, grads)
        m = jax.tree.map(lambda a, x: b1 * a + (1 - b1) * x, m, grads)
        v = jax.tree.map(lambda a, x: b2 * a + (1 - b2) * x * x, v, grads)

        def new_p(p, a, b):
            pf = p.astype(F32)
            upd = (a / (1 - b1 ** t)) / (jnp.sqrt(b / (1 - b2 ** t)) + eps)
            return (pf - lr * (upd + wd * pf)).astype(p.dtype)

        params = jax.tree.map(new_p, params, m, v)
        return params, m, v, loss, _norms(grads), raw

    return jax.jit(step, donate_argnums=(1, 2))


def train_reference(cfg, opt, seed, batches, precision="f32"):
    """Run ``len(batches)`` steps from the seed's weights.

    ``batches``: (tokens (B, S), labels (B, S)) pairs. Returns the per-step
    losses, the per-leaf norms of the first gradient as the optimizer gets
    it (clipped) and as the loss gives it (raw), and, leaf by leaf as
    ``flat`` names them, on the device: the first gradient as the optimizer
    gets it (``grad_elems``), the weights before the first step (``p0``)
    and after the last (``p_end``).
    """
    words = jnp.asarray(seed_words(seed))
    params = jax.jit(functools.partial(make_all, cfg))(words)
    p0 = params
    m = jax.tree.map(lambda p: jnp.zeros(p.shape, F32), params)
    v = jax.tree.map(lambda p: jnp.zeros(p.shape, F32), params)
    step = _step_fn(cfg, opt, precision)
    losses, first, grad_elems = [], None, None
    for i, (tokens, labels) in enumerate(batches):
        params, m, v, loss, clipped, raw = step(
            params, m, v, tokens, labels, jnp.asarray(i + 1.0, F32))
        losses.append(float(loss))
        if first is None:
            first = (clipped, raw)
            # m starts at zero, so after one step it is (1 - b1) g
            grad_elems = jax.jit(lambda t: jax.tree.map(
                lambda a: a / (1.0 - opt["b1"]), t))(m)
    del m, v
    as_float = lambda d: {k: float(x) for k, x in d.items()}
    return {"losses": losses, "grad": as_float(first[0]),
            "raw_grad": as_float(first[1]), "grad_elems": flat(grad_elems),
            "p0": flat(p0), "p_end": flat(params)}
