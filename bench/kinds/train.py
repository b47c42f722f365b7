"""Training traffic: the program's compiled train step, fed batches of token
ids drawn from the seed, for a fixed wall-clock window.

Set-up builds one object, the compiled step with its state (weights made on
the device from the seed in one jitted call, AdamW state from the program),
and drives it through the first ``check_steps`` steps with the window's own
call and feed. Those steps are what ``correct`` compares with the reference:
the loss of each, the norm of each leaf of the first gradient as the
optimizer got it (read from its first moment after one step), and the norm
of each leaf's change over the steps (``compare``). The window then
continues from that same state.

The traffic file gives the batch, the sequence length, the optimizer and
``check_steps``. Token ids are uniform over the vocabulary; every row of
every step differs.
"""
from __future__ import annotations

import functools
import math
import statistics
import time

import jax
import jax.numpy as jnp

from bench import harness

_TAG_DATA = 0xDA7A
IN_FLIGHT = 8


def compiled(step_fn, state, batch):
    """(the step compiled for these shapes, its device memory in bytes as
    the compiler plans it). The window and the check steps drive this same
    executable. A step that is not a jitted function (a test's planted
    fault) is returned as it is, with no plan."""
    if not hasattr(step_fn, "lower"):
        return step_fn, {}
    exe = step_fn.lower(state, batch).compile()
    ma = exe.memory_analysis()
    if ma is None:
        return exe, {}
    plan = {"arguments": int(ma.argument_size_in_bytes),
            "outputs": int(ma.output_size_in_bytes),
            "aliased": int(ma.alias_size_in_bytes),
            "temporaries": int(ma.temp_size_in_bytes)}
    plan["peak"] = (plan["arguments"] + plan["outputs"] - plan["aliased"]
                    + plan["temporaries"])
    return exe, plan


def batch_fn(vocab: int, batch: int, seq: int, root_key):
    """jitted (seed words, step) -> {"tokens", "labels"} (batch, seq) int32:
    next-token pairs of one uniform draw of seq + 1 ids per row.
    ``root_key(words, tag)`` is the reference module's key derivation."""

    @jax.jit
    def make(words, step):
        key = jax.random.fold_in(root_key(words, _TAG_DATA), step)
        ids = jax.random.randint(key, (batch, seq + 1), 0, vocab, jnp.int32)
        return {"tokens": ids[:, :-1], "labels": ids[:, 1:]}

    return make


def _kept(ref_grad: dict, rule: float):
    """Leaves whose reference gradient is not nought to rounding: at least
    ``rule`` times the median leaf's. Others move under Adam by round-off
    alone (a key bias under softmax, for one) and are left out by this rule,
    never by name."""
    med = statistics.median(ref_grad.values())
    return [k for k, v in ref_grad.items() if v >= rule * med], med


def leaf_gaps(prog: dict, ref: dict, kept, median_floor: bool = True
              ) -> dict:
    """|‖program leaf‖ − ‖reference leaf‖| of each kept leaf, against the
    reference leaf's norm or, with ``median_floor``, the median leaf's where
    that is larger, since some gradients are all but zero. A gap that is not
    finite reads infinite."""
    floor = statistics.median(ref[k] for k in kept) if median_floor else 0.0
    out = {}
    for k in kept:
        gap = abs(prog[k] - ref[k]) / max(ref[k], floor)
        out[k] = gap if math.isfinite(gap) else math.inf
    return out


@jax.jit
def _masked_change(g, p0, p_ref, p_prog, threshold):
    """(‖reference change‖, ‖program change‖, elements kept) over the
    elements of one leaf whose reference gradient reaches ``threshold``."""
    keep = jnp.abs(g) >= threshold
    f32 = jnp.float32

    def norm(p):
        d = jnp.where(keep, p.astype(f32) - p0.astype(f32), 0.0)
        return jnp.sqrt(jnp.sum(d * d))
    return norm(p_ref), norm(p_prog), jnp.sum(keep)


def change_norms(prog_end: dict, ref: dict, kept, rule: float):
    """Per kept leaf, the norms of the reference's and the program's change
    over the check steps, over the elements whose reference first gradient
    is at least ``rule`` times the median leaf's root mean square element.
    The others move under Adam by round-off alone: a gradient that is nought
    in exact arithmetic (the dimensions of a key bias that the rotary
    embedding leaves unrotated, since softmax ignores a shift shared by all
    keys) reads as bfloat16 round-off, which Adam scales up to a step as
    large as any. Returns ({leaf: (ref, prog)}, elements left out)."""
    g, p0, p_ref = ref["grad_elems"], ref["p0"], ref["p_end"]
    rms = statistics.median(ref["grad"][k] / math.sqrt(g[k].size)
                            for k in kept)
    out, left_out = {}, 0
    for k in kept:
        n_ref, n_prog, n_kept = _masked_change(g[k], p0[k], p_ref[k],
                                               prog_end[k], rule * rms)
        out[k] = (float(n_ref), float(n_prog))
        left_out += g[k].size - int(n_kept)
    return out, left_out


def compare(prog: dict, ref: dict, rule: float) -> dict:
    """The numbers ``correct`` compares, from a program reading and a
    reference reading (``prog``: ``losses``, ``grad`` norms by leaf and the
    weights after the check steps, ``p_end``, leaf by leaf; ``ref``: as
    ``train_reference`` returns it):

    * ``loss_gap``: the largest relative gap of a step's loss;
    * ``grad_gap``: the worst leaf's gap of the first gradient's norm;
    * ``change_gap``: the worst leaf's gap of the norm of the weights'
      change over the check steps, over the elements ``change_norms``
      keeps.

    Gaps of norms are against the reference leaf's norm or the median
    leaf's, whichever is larger.
    """
    kept, _ = _kept(ref["grad"], rule)
    grad = leaf_gaps(prog["grad"], ref["grad"], kept)
    norms, left_out = change_norms(prog["p_end"], ref, kept, rule)
    change = leaf_gaps({k: v[1] for k, v in norms.items()},
                       {k: v[0] for k, v in norms.items()}, kept)
    loss_gap = max(abs(a - b) / abs(b)
                   for a, b in zip(prog["losses"], ref["losses"]))
    worst_change = max(change, key=change.get)
    return {"loss_gap": loss_gap,
            "grad_gap": max(grad.values()),
            "change_gap": change[worst_change],
            "worst_grad_leaf": max(grad, key=grad.get),
            "worst_change_leaf": worst_change,
            "leaves_kept": len(kept), "leaves": len(ref["grad"]),
            "elements_left_out": left_out}


def run(spec, seed: int, seconds: float, tracer, t_start: float):
    cfg, tr = spec.cfg, spec.traffic
    ad, ref_mod = harness.adapter(spec), harness.model(spec)
    n_layers = cfg["num_hidden_layers"]
    b, s = tr["batch"], tr["seq"]

    # ---- set-up: one compiled step, one state
    mc = ad.program_config(cfg)
    tcfg = ad.train_config(tr)
    words = jnp.asarray(ref_mod.seed_words(seed))
    params = jax.jit(lambda w: ad.to_program(mc, ref_mod.make_stacked(cfg, w)))(
        words)
    state = ad.init_state(params, tcfg)
    del params
    feed = batch_fn(cfg["vocab_size"], b, s, ref_mod.root_key)
    step_fn, program_bytes = compiled(ad.build_train_step(mc, tcfg), state,
                                      feed(words, 0))
    grad_norms = jax.jit(functools.partial(ad.first_grad_norms, tcfg=tcfg,
                                           n_layers=n_layers))

    losses = []
    first_grad = None
    for i in range(tr["check_steps"]):
        state, met = step_fn(state, feed(words, i))
        losses.append(met["loss"])
        if i == 0:
            first_grad = grad_norms(state)
    prog = {"losses": [float(x) for x in losses],
            "grad": {k: float(v) for k, v in first_grad.items()},
            # the weights as the check steps left them, kept on the host
            # until the reference has run
            "p_end": jax.device_get(ad.leaves(ad.params_of(state),
                                              n_layers))}
    setup_s = time.perf_counter() - t_start

    # ---- window: steps until --seconds have passed. Up to IN_FLIGHT steps
    # are queued on the device ahead of the host, which waits only for the
    # step IN_FLIGHT back and reads no value until the window has closed, so
    # a pause of the host shorter than the queue leaves the device busy.
    first = tr["check_steps"]
    window_losses = []
    tracer.begin()
    harness.COMPILES.start()
    t0 = time.perf_counter()
    n = 0
    while True:
        with tracer.span("data"):
            batch = feed(words, first + n)
        with tracer.span("step_dispatch"):
            state, met = step_fn(state, batch)
        window_losses.append(met["loss"])
        n += 1
        if n > IN_FLIGHT:
            with tracer.span("sync"):
                window_losses[n - 1 - IN_FLIGHT].block_until_ready()
        if time.perf_counter() - t0 >= seconds:
            break
    with tracer.span("sync"):
        jax.block_until_ready((state, window_losses))
    t_end = time.perf_counter()
    compiles = harness.COMPILES.stop()
    tracer.end()
    window_s = t_end - t0
    window_losses = [float(x) for x in jax.device_get(window_losses)]
    mem = harness.memory_peak_bytes(spec.workload["chips"])
    del state, met, batch

    # ---- reference, once the window has closed and the state is freed
    batches = [(feed(words, i)["tokens"], feed(words, i)["labels"])
               for i in range(tr["check_steps"])]
    ref = ref_mod.train_reference(cfg, tr["optimizer"], seed, batches)
    got = compare(prog, ref, tr["leaf_rule"])
    ref_losses = ref["losses"]
    del ref

    failed = sum(1 for x in window_losses if not math.isfinite(x))
    tokens = n * b * s
    notes = [f"train: {n} steps of {b}x{s} tokens in {window_s:.3f} s "
             f"window, setup {setup_s:.3f} s; compiled step {program_bytes}; "
             f"leaves compared "
             f"{got['leaves_kept']} of {got['leaves']}, worst gradient leaf "
             f"{got['worst_grad_leaf']}, worst change leaf "
             f"{got['worst_change_leaf']}, elements left out of the change "
             f"{got['elements_left_out']}; program losses {prog['losses']} "
             f"reference {ref_losses}; {compiles}"]
    return harness.Outcome(
        attempted=n, failed=failed,
        e2e={"train_tokens_per_s": tokens / window_s, "setup_s": setup_s},
        checks={k: got[k] for k in ("loss_gap", "grad_gap", "change_gap")},
        memory_peak_bytes=mem,
        program_bytes=program_bytes,
        counts={"steps": n, "tokens_per_step": b * s, "batch": b, "seq": s,
                "window_s": window_s},
        notes=notes)
