"""Plain reference of a decoder whose layers mix sliding-window and full
attention and whose every MLP is a routed mixture of experts, held here as
one chip's share of an expert-parallel deployment; and its weights.

This is the yardstick's own model: the published equations in float32 at
``highest`` matmul precision, written from the model's configuration and
never from the program under test. It imports nothing of ``repro``.

Covers Mellum2-12B-A2.5B (hf:JetBrains/Mellum2-12B-A2.5B-Instruct), from
its configuration file:

  * pre-norm RMSNorm, grouped-query attention without biases, an untied
    head;
  * ``layer_types``: a ``sliding_attention`` layer sees the keys in
    ``(q - sliding_window, q]``, a dense mask; a ``full_attention`` layer
    is causal;
  * each layer type's rotary (``rope_parameters``): ``default`` (θ^(-2i/d))
    or ``yarn`` (HF transformers' rule: interpolated and extrapolated
    frequencies blended by a linear ramp between the correction dims of
    ``beta_fast`` and ``beta_slow`` rotations at the original length; cos
    and sin times ``attention_factor``), HF rotate-half;
  * the expert layer: a float32 softmax router over all
    ``router_experts``, the top ``num_experts_per_tok`` renormalised over
    themselves (``norm_topk_prob``), SwiGLU experts of width
    ``moe_intermediate_size``. Only the ``held_experts`` are computed,
    densely for every token, each weighted by its gate (0 where the token
    did not route to it): the part of the layer this chip gives;
  * the loss: mean next-token cross-entropy plus ``router_aux_loss_coef``
    times a Switch-style balance loss, per layer
    ``router_experts · Σ_e f_e · P_e`` over the batch's tokens (f_e: the
    share of tokens routing a slot to e; P_e: the mean router
    probability), summed over the layers.

Departures, all deliberate: weights are random from the seed
(``dense_decoder``'s rule and seeding; the embedding at
``embedding_init_std``, the rest at ``initializer_range``), each kept in
its storage type (``param_dtype``, ``norm_dtype``, ``router_dtype``) and
rounded to it after an update; attention is computed in query blocks and layers under
``jax.checkpoint``, and blocks and experts in loops, so that it fits one
chip and compiles in reasonable time. ``precision="fp8"`` is the
control: every matrix product on float8 e4m3 operands.
"""
from __future__ import annotations

import functools
import math
from typing import Dict

import jax
import jax.numpy as jnp

from bench.models.dense_decoder import (_leaf, flat, matmul, root_key,
                                        seed_words)

F32 = jnp.float32
__all__ = ["seed_words", "root_key", "make_all", "make_stacked",
           "batch_loss", "train_reference", "dims"]

_TAG_WEIGHTS = 0x5EED
_GLOBAL_LEAVES = ("embed", "head", "lnf_scale")
_LAYER_LEAVES = ("ln1_scale", "wq", "wk", "wv", "wo", "ln2_scale", "router",
                 "w_gate", "w_up", "w_down")


# --------------------------------------------------------------------------- #
# shapes
# --------------------------------------------------------------------------- #
def dims(cfg: dict) -> dict:
    """The sizes the equations need, from a configuration file's keys."""
    n = cfg["num_hidden_layers"]
    types = cfg["layer_types"][:n]
    held = tuple(cfg["held_experts"])
    assert len(held) == cfg["num_experts"], "held_experts gives num_experts ids"
    return dict(d=cfg["hidden_size"], h=cfg["num_attention_heads"],
                hk=cfg["num_key_value_heads"], hd=cfg["head_dim"],
                f=cfg["moe_intermediate_size"], v=cfg["vocab_size"],
                layers=n, types=types, window=cfg["sliding_window"],
                eps=cfg["rms_norm_eps"], rope=cfg["rope_parameters"],
                e=cfg["router_experts"], k=cfg["num_experts_per_tok"],
                held=held, renorm=bool(cfg["norm_topk_prob"]),
                aux_coef=cfg["router_aux_loss_coef"])


def layer_shapes(cfg: dict) -> Dict[str, tuple]:
    m = dims(cfg)
    d, h, hk, hd, f, j = m["d"], m["h"], m["hk"], m["hd"], m["f"], len(m["held"])
    return {"ln1_scale": (d,), "wq": (d, h * hd), "wk": (d, hk * hd),
            "wv": (d, hk * hd), "wo": (h * hd, d), "ln2_scale": (d,),
            "router": (d, m["e"]), "w_gate": (j, d, f), "w_up": (j, d, f),
            "w_down": (j, f, d)}


def global_shapes(cfg: dict) -> Dict[str, tuple]:
    m = dims(cfg)
    return {"embed": (m["v"], m["d"]), "head": (m["d"], m["v"]),
            "lnf_scale": (m["d"],)}


def storage_dtype(cfg: dict, name: str):
    if name.endswith("_scale"):
        return jnp.dtype(cfg["norm_dtype"])
    if name == "router":
        return jnp.dtype(cfg["router_dtype"])
    return jnp.dtype(cfg["param_dtype"])


# --------------------------------------------------------------------------- #
# weights from the seed
# --------------------------------------------------------------------------- #
def _make(cfg: dict, words, layer, shapes, names) -> Dict[str, jax.Array]:
    k = jax.random.fold_in(root_key(words, _TAG_WEIGHTS), layer)
    std = {"embed": cfg["embedding_init_std"]}
    return {n: _leaf(jax.random.fold_in(k, names.index(n)), n, s,
                     std.get(n, cfg["initializer_range"]))
            .astype(storage_dtype(cfg, n)) for n, s in shapes.items()}


def make_global(cfg: dict, words) -> Dict[str, jax.Array]:
    return _make(cfg, words, 0, global_shapes(cfg), _GLOBAL_LEAVES)


def make_layer(cfg: dict, words, layer) -> Dict[str, jax.Array]:
    return _make(cfg, words, layer + 1, layer_shapes(cfg), _LAYER_LEAVES)


def make_all(cfg: dict, words) -> dict:
    """``{"global": {...}, "layers": [{...}, ...]}``, under ``jax.jit``."""
    return {"global": make_global(cfg, words),
            "layers": [make_layer(cfg, words, i)
                       for i in range(cfg["num_hidden_layers"])]}


def make_stacked(cfg: dict, words) -> dict:
    """The same leaves with the layers stacked on a leading axis."""
    layers = jnp.arange(cfg["num_hidden_layers"], dtype=jnp.int32)
    return {"global": make_global(cfg, words),
            "layers": jax.vmap(lambda i: make_layer(cfg, words, i))(layers)}


# --------------------------------------------------------------------------- #
# the equations
# --------------------------------------------------------------------------- #
def _rms(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def inv_freq(rp: dict, dim: int):
    """(dim/2,) inverse frequencies and the cos/sin factor of one layer
    type's ``rope_parameters`` entry."""
    theta = float(rp["rope_theta"])
    base = theta ** (jnp.arange(0, dim, 2, dtype=F32) / dim)
    if rp["rope_type"] == "default":
        return 1.0 / base, 1.0
    assert rp["rope_type"] == "yarn", rp
    factor = float(rp["factor"])
    orig = rp["original_max_position_embeddings"]

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(theta))

    lo = max(math.floor(corr(rp["beta_fast"])), 0)
    hi = min(math.ceil(corr(rp["beta_slow"])), dim - 1)
    hi = hi + 0.001 if lo == hi else hi
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=F32) - lo) / (hi - lo), 0, 1)
    keep = 1.0 - ramp                    # share of the extrapolated frequency
    inv = (1.0 / (factor * base)) * (1.0 - keep) + (1.0 / base) * keep
    return inv, float(rp["attention_factor"])


def _rope(x, pos, inv, scale):
    """HF rotate-half rotary; x (S, H, hd), pos (S,)."""
    ang = pos.astype(F32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :] * scale
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :] * scale
    half = x.shape[-1] // 2
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rotated * sin


def _attention(q, k, v, m, window, precision, q_block):
    """q (S, H, hd), k/v (S, Hk, hd) -> (S, H*hd): causal, or the keys in
    (q - window, q] where ``window``, as a dense mask over the keys a query
    block can see: every key, or the band of ``window - 1`` keys before the
    block and the block's own. Blocks run in a loop (``lax.map``) under
    ``jax.checkpoint``."""
    s, h, hd = q.shape
    g = h // m["hk"]
    kh = jnp.repeat(k, g, axis=1).transpose(1, 2, 0)     # (H, hd, S)
    vh = jnp.repeat(v, g, axis=1).transpose(1, 0, 2)     # (H, S, hd)
    nb = s // q_block
    qh = (q.transpose(1, 0, 2) / math.sqrt(hd)).reshape(
        h, nb, q_block, hd).transpose(1, 0, 2, 3)        # (nb, H, qb, hd)
    pad = window - 1 if window else 0
    band = window - 1 + q_block if window else s
    kh = jnp.pad(kh, ((0, 0), (0, 0), (pad, 0)))         # key j at j + pad
    vh = jnp.pad(vh, ((0, 0), (pad, 0), (0, 0)))

    @jax.checkpoint
    def block(args):
        qb, start = args
        lo = start if window else 0                      # padded coordinates
        kb = jax.lax.dynamic_slice_in_dim(kh, lo, band, axis=2)
        vb = jax.lax.dynamic_slice_in_dim(vh, lo, band, axis=1)
        kpos = lo - pad + jnp.arange(band)
        qpos = start + jnp.arange(q_block)
        seen = (qpos[:, None] >= kpos[None, :]) & (kpos[None, :] >= 0)
        if window:
            seen = seen & (kpos[None, :] > qpos[:, None] - window)
        sc = matmul(qb, kb, precision)                   # (H, qb, band)
        p = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), axis=-1)
        return matmul(p, vb, precision)                  # (H, qb, hd)

    out = jax.lax.map(block, (qh, jnp.arange(nb) * q_block))  # (nb, H, qb, hd)
    return out.transpose(0, 2, 1, 3).reshape(s, h * hd)


def _experts(p, h, m, precision):
    """The held experts' part of the layer for rows h (S, D), summed over
    the held experts in ascending order, and the routing counts and
    probability sums over the rows (E,) each."""
    probs = jax.nn.softmax(matmul(h, p["router"], precision), axis=-1)
    top, idx = jax.lax.top_k(probs, m["k"])
    if m["renorm"]:
        top = top / top.sum(-1, keepdims=True)

    def one(y, ex):
        e, wg, wu, wd = ex
        gate = jnp.sum(jnp.where(idx == e, top, 0.0), -1)          # (S,)
        a = matmul(h, wg, precision)
        b = matmul(h, wu, precision)
        return y + gate[:, None] * matmul(jax.nn.silu(a) * b, wd,
                                          precision), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h), (
        jnp.asarray(m["held"], jnp.int32), p["w_gate"], p["w_up"],
        p["w_down"]))
    counts = jax.nn.one_hot(idx, m["e"], dtype=F32).sum((0, 1))
    return y, jax.lax.stop_gradient(counts), probs.sum(0)


def layer_forward(p, x, pos, layer: int, cfg, precision="f32", q_block=512):
    """One layer on one sequence: x (S, D) float32 -> (x, counts, prob
    sums)."""
    m = dims(cfg)
    f = {n: a.astype(F32) for n, a in p.items()}
    kind = m["types"][layer]
    inv, scale = inv_freq(m["rope"][kind], m["hd"])
    s = x.shape[0]
    h = _rms(x, f["ln1_scale"], m["eps"])
    q = _rope(matmul(h, f["wq"], precision).reshape(s, m["h"], m["hd"]),
              pos, inv, scale)
    k = _rope(matmul(h, f["wk"], precision).reshape(s, m["hk"], m["hd"]),
              pos, inv, scale)
    v = matmul(h, f["wv"], precision).reshape(s, m["hk"], m["hd"])
    window = m["window"] if kind == "sliding_attention" else 0
    a = _attention(q, k, v, m, window, precision, min(q_block, s))
    x = x + matmul(a, f["wo"], precision)
    y, counts, psum = _experts(f, _rms(x, f["ln2_scale"], m["eps"]), m,
                               precision)
    return x + y, counts, psum


def sequence_sums(params, tokens, labels, cfg, precision="f32",
                  row_block=1024):
    """One sequence (S,) -> (summed cross-entropy, per-layer routing counts
    (L, E), per-layer router probability sums (L, E))."""
    g, layers = params["global"], params["layers"]
    m = dims(cfg)
    x = jnp.take(g["embed"].astype(F32), tokens, axis=0)
    pos = jnp.arange(tokens.shape[0])
    counts, psums = [], []
    for i, p in enumerate(layers):
        x, c, ps = jax.checkpoint(functools.partial(
            layer_forward, layer=i, cfg=cfg, precision=precision))(p, x, pos)
        counts.append(c)
        psums.append(ps)
    hn = _rms(x, g["lnf_scale"].astype(F32), m["eps"])
    head = g["head"].astype(F32)

    @jax.checkpoint
    def ce(hb, lb):
        lg = matmul(hb, head, precision)
        gold = jnp.take_along_axis(lg, lb[:, None], axis=-1)[:, 0]
        return jnp.sum(jax.nn.logsumexp(lg, axis=-1) - gold)

    n = tokens.shape[0]
    blk = min(row_block, n)
    per_block = jax.lax.map(lambda a: ce(*a), (hn.reshape(n // blk, blk, -1),
                                               labels.reshape(n // blk, blk)))
    return jnp.sum(per_block), jnp.stack(counts), jnp.stack(psums)


# --------------------------------------------------------------------------- #
# training: loss, gradients and AdamW
# --------------------------------------------------------------------------- #
def _norms(tree) -> Dict[str, jax.Array]:
    return {n: jnp.sqrt(jnp.sum(jnp.square(a.astype(F32))))
            for n, a in flat(tree).items()}


def batch_loss(params, tokens, labels, cfg, precision="f32"):
    """Mean cross-entropy over every token of the batch (B, S) + the aux
    coefficient × the balance loss over the batch's tokens. Rows are summed
    one at a time under ``jax.checkpoint``."""
    m = dims(cfg)
    row = jax.checkpoint(functools.partial(
        sequence_sums, cfg=cfg, precision=precision))

    def body(acc, tl):
        return jax.tree.map(jnp.add, acc, row(params, *tl)), None

    zero = (jnp.zeros((), F32), jnp.zeros((m["layers"], m["e"]), F32),
            jnp.zeros((m["layers"], m["e"]), F32))
    (ce, counts, psums), _ = jax.lax.scan(body, zero, (tokens, labels))
    n = tokens.size
    aux = m["e"] * jnp.sum((counts / n) * (psums / n))
    return ce / n + m["aux_coef"] * aux


def _step_fn(cfg, opt, precision):
    """One step of :func:`batch_loss` and AdamW as in ``dense_decoder``
    (global-norm clipping, bias correction, decoupled decay, float32
    moments, parameters rounded to their storage type)."""
    b1, b2, eps = opt["b1"], opt["b2"], opt["eps"]
    lr, wd, clip = opt["lr"], opt["weight_decay"], opt["clip_norm"]

    def step(params, mo, ve, tokens, labels, t):
        loss, grads = jax.value_and_grad(batch_loss)(params, tokens, labels,
                                                     cfg, precision)
        grads = jax.tree.map(lambda x: x.astype(F32), grads)
        gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(grads)))
        scale = jnp.minimum(1.0, clip / jnp.maximum(gnorm, 1e-9))
        raw = _norms(grads)
        grads = jax.tree.map(lambda x: x * scale, grads)
        mo = jax.tree.map(lambda a, x: b1 * a + (1 - b1) * x, mo, grads)
        ve = jax.tree.map(lambda a, x: b2 * a + (1 - b2) * x * x, ve, grads)

        def new_p(p, a, b):
            pf = p.astype(F32)
            upd = (a / (1 - b1 ** t)) / (jnp.sqrt(b / (1 - b2 ** t)) + eps)
            return (pf - lr * (upd + wd * pf)).astype(p.dtype)

        params = jax.tree.map(new_p, params, mo, ve)
        return params, mo, ve, loss, _norms(grads), raw

    return jax.jit(step, donate_argnums=(1, 2))


def train_reference(cfg, opt, seed, batches, precision="f32"):
    """Run ``len(batches)`` steps from the seed's weights, at ``highest``
    matmul precision. Returns what ``dense_decoder.train_reference``
    returns: the losses, the first gradient's per-leaf norms (clipped and
    raw), and leaf by leaf the first clipped gradient, the weights before
    the first step and after the last."""
    with jax.default_matmul_precision("highest"):
        words = jnp.asarray(seed_words(seed))
        params = jax.jit(functools.partial(make_all, cfg))(words)
        p0 = params
        mo = jax.tree.map(lambda p: jnp.zeros(p.shape, F32), params)
        ve = jax.tree.map(lambda p: jnp.zeros(p.shape, F32), params)
        step = _step_fn(cfg, opt, precision)
        losses, first, grad_elems = [], None, None
        for i, (tokens, labels) in enumerate(batches):
            params, mo, ve, loss, clipped, raw = step(
                params, mo, ve, tokens, labels, jnp.asarray(i + 1.0, F32))
            losses.append(float(loss))
            if first is None:
                first = (clipped, raw)
                grad_elems = jax.jit(lambda t: jax.tree.map(
                    lambda a: a / (1.0 - opt["b1"]), t))(mo)
        del mo, ve
    as_float = lambda d: {k: float(x) for k, x in d.items()}
    return {"losses": losses, "grad": as_float(first[0]),
            "raw_grad": as_float(first[1]), "grad_elems": flat(grad_elems),
            "p0": flat(p0), "p_end": flat(params)}
