"""Training-lifecycle drivers returning digest chains (the conformance layer).

Each driver executes the *real* ``train/step.py`` under a small config and
returns a :class:`repro.verify.digest.DigestChain` with one record per
completed optimizer step:

* :func:`run_straight`        — N uninterrupted steps;
* :func:`run_with_crash_resume` — k steps → async checkpoint → simulated crash
  (state and compiled step discarded) → fresh build → restore → N−k steps;
* :func:`run_elastic_reshard` — k steps → state placed on mesh A under rule
  set A → checkpoint → restore **re-sharded** onto mesh B under rule set B
  (different device count) → state pulled back for compute → N−k steps fed by
  a *re-split* data pipeline (host_count change), with the host slices
  digest-checked against the single-host global batch.

The contract proven by tests/test_lifecycle_bitwise.py: all three chains are
bitwise identical, per config cell, across the MATRIX axes (microbatching,
int8 grad compression + error feedback, remat policy, GQA, MoE block pattern,
bf16 optimizer state).  What may legitimately change bits is the *compute*
layout (mesh rules that re-associate contractions) and the schedule choice —
see README §Reproducibility contract; this module keeps compute placement
fixed and scopes elasticity to state placement + persistence + data re-split,
which is exactly what ``ckpt/checkpoint.py`` promises.

Runnable as a module for the subprocess conformance test (forced multi-device
CPU) and the CI digest artifact:

    PYTHONPATH=src python -m repro.verify.lifecycle --cells base,int8 \
        --out digest_conformance.json
"""
from __future__ import annotations

import dataclasses
import json
import os
import tempfile
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.ckpt import checkpoint as C
from repro.configs import registry
from repro.data.pipeline import DataConfig, make_source
from repro.train import optimizer as O
from repro.train import step as S
from repro.verify.digest import DigestChain, batch_digest


@dataclasses.dataclass(frozen=True)
class LifecycleConfig:
    arch: str = "stablelm-1.6b"
    steps: int = 5
    batch: int = 4
    seq: int = 16
    seed: int = 0
    microbatches: int = 1
    grad_compression: Optional[str] = None
    remat: bool = False
    remat_policy: str = "none"
    opt_state_dtype: str = "float32"
    overrides: Tuple[Tuple[str, object], ...] = ()   # ModelConfig.reduced kw

    def model_config(self):
        return registry.get(self.arch).reduced(**dict(self.overrides))

    def train_config(self) -> S.TrainConfig:
        return S.TrainConfig(
            opt=O.OptConfig(total_steps=self.steps,
                            state_dtype=self.opt_state_dtype),
            microbatches=self.microbatches, remat=self.remat,
            remat_policy=self.remat_policy,
            grad_compression=self.grad_compression, seed=self.seed)

    def data_config(self, host_index: int = 0, host_count: int = 1):
        return DataConfig(seed=self.seed, batch=self.batch, seq=self.seq,
                          vocab=self.model_config().vocab,
                          host_index=host_index, host_count=host_count)


def _build(lc: LifecycleConfig):
    cfg, tcfg = lc.model_config(), lc.train_config()
    step_fn = jax.jit(S.make_train_step(cfg, tcfg))
    return cfg, tcfg, step_fn


def _init(lc: LifecycleConfig, cfg, tcfg):
    return S.init_state(cfg, tcfg, jax.random.PRNGKey(lc.seed))


# ----------------------------------------------------------------- scenarios
def run_straight(lc: LifecycleConfig) -> DigestChain:
    """N uninterrupted steps; digests the full state per step."""
    cfg, tcfg, step_fn = _build(lc)
    state = _init(lc, cfg, tcfg)
    data = make_source(lc.data_config())
    chain = DigestChain()
    for step in range(lc.steps):
        state, _ = step_fn(state, data.batch(step))
        chain.append(step + 1, state)
    return chain


def run_with_crash_resume(lc: LifecycleConfig, ckpt_dir: str,
                          crash_at: int) -> DigestChain:
    """k steps → async save → crash (everything dropped) → restore → N−k."""
    cfg, tcfg, step_fn = _build(lc)
    state = _init(lc, cfg, tcfg)
    data = make_source(lc.data_config())
    chain = DigestChain()
    for step in range(crash_at):
        state, _ = step_fn(state, data.batch(step))
        chain.append(step + 1, state)
    C.save(ckpt_dir, crash_at, state, async_=True).join()
    del state, step_fn                      # ---- simulated hard crash ----

    cfg, tcfg, step_fn = _build(lc)         # fresh compile, fresh everything
    target = _init(lc, cfg, tcfg)
    k = C.latest_step(ckpt_dir)
    assert k == crash_at, (k, crash_at)
    state = C.restore(ckpt_dir, k, target)
    data = make_source(lc.data_config())    # stateless sampler: no replay
    for step in range(k, lc.steps):
        state, _ = step_fn(state, data.batch(step))
        chain.append(step + 1, state)
    return chain


def _state_shardings(cfg, tcfg, state, mesh, rule_name: str):
    """NamedSharding tree for ``state`` under ``rule_name`` on ``mesh``
    (specs that don't divide the leaf shapes are dropped per-axis)."""
    from jax.sharding import NamedSharding
    from repro.dist.sharding import RULE_SETS, sanitize_pspecs

    pspecs = S.state_pspecs(cfg, tcfg, RULE_SETS[rule_name](False))
    pspecs = sanitize_pspecs(pspecs, state, mesh)
    return jax.tree.map(lambda sp: NamedSharding(mesh, sp), pspecs,
                        is_leaf=lambda x: isinstance(
                            x, jax.sharding.PartitionSpec))


def _make_mesh(n_devices: int):
    devs = jax.devices()[:n_devices]
    return jax.sharding.Mesh(np.array(devs).reshape(len(devs), 1),
                             ("data", "model"))


def run_elastic_reshard(lc: LifecycleConfig, ckpt_dir: str, reshard_at: int,
                        *, n_dev_a: Optional[int] = None,
                        n_dev_b: Optional[int] = None,
                        rules_a: str = "fsdp_tp", rules_b: str = "tp",
                        host_count_b: int = 2) -> DigestChain:
    """k steps → save from mesh-A-sharded state → restore re-sharded onto a
    different mesh/rule set → continue with a re-split data pipeline.

    Compute placement stays fixed (default device) — elasticity here is
    state placement + persistence + data host split, the bitwise-invariant
    subset; see the module docstring for what legitimately changes bits.
    """
    n_avail = len(jax.devices())
    n_a = n_dev_a or min(2, n_avail)
    n_b = n_dev_b or n_avail
    cfg, tcfg, step_fn = _build(lc)
    state = _init(lc, cfg, tcfg)
    data = make_source(lc.data_config())
    chain = DigestChain()
    for step in range(reshard_at):
        state, _ = step_fn(state, data.batch(step))
        chain.append(step + 1, state)

    # place the live state on mesh A under rule set A, save *from* there
    mesh_a = _make_mesh(n_a)
    state_a = jax.device_put(
        state, _state_shardings(cfg, tcfg, state, mesh_a, rules_a))
    C.save(ckpt_dir, reshard_at, state_a, async_=True).join()
    del state, state_a, step_fn             # ---- simulated scale event ----

    # restart on a "different cluster": new mesh size, new rule set
    cfg, tcfg, step_fn = _build(lc)
    target = _init(lc, cfg, tcfg)
    mesh_b = _make_mesh(n_b)
    shardings_b = _state_shardings(cfg, tcfg, target, mesh_b, rules_b)
    state = C.restore(ckpt_dir, reshard_at, target, shardings=shardings_b)
    state = jax.device_get(state)           # pull back to the compute layout

    # elastic data re-split: host slices must partition the global batch
    hosts = ([make_source(lc.data_config(i, host_count_b))
              for i in range(host_count_b)]
             if lc.batch % host_count_b == 0 else None)
    single = make_source(lc.data_config())
    for step in range(reshard_at, lc.steps):
        batch = single.batch(step)
        if hosts is not None:
            slices = [h.batch(step) for h in hosts]
            glued = jax.tree.map(
                lambda *xs: jnp.concatenate(xs, axis=0), *slices)
            if batch_digest(glued) != batch_digest(batch):
                raise AssertionError(
                    f"host re-split changed the global batch at step {step}")
            batch = glued
        state, _ = step_fn(state, batch)
        chain.append(step + 1, state)
    return chain


def stream_chain(lc: LifecycleConfig, *, host_count: int = 1) -> DigestChain:
    """Token-stream digest chain: one global-batch digest per step."""
    chain = DigestChain()
    if host_count == 1:
        src = make_source(lc.data_config())
        for step in range(lc.steps):
            chain.append_digest(step, batch_digest(src.batch(step)))
        return chain
    hosts = [make_source(lc.data_config(i, host_count))
             for i in range(host_count)]
    for step in range(lc.steps):
        glued = jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0),
                             *[h.batch(step) for h in hosts])
        chain.append_digest(step, batch_digest(glued))
    return chain


# ------------------------------------------------------------------- matrix
MATRIX: Dict[str, LifecycleConfig] = {
    "base":    LifecycleConfig(),
    "mb4":     LifecycleConfig(microbatches=4),
    "int8":    LifecycleConfig(grad_compression="int8"),
    "remat":   LifecycleConfig(remat=True, remat_policy="dots"),
    "gqa":     LifecycleConfig(overrides=(("n_kv_heads", 2),)),
    "moe":     LifecycleConfig(arch="phi3.5-moe-42b-a6.6b"),
    "bf16opt": LifecycleConfig(opt_state_dtype="bfloat16"),
    # sentinel cell: not a train-lifecycle chain — run_cell dispatches it to
    # run_train_serve_parity (train forward ≡ serve chunked prefill, bitwise)
    "train_serve_parity": LifecycleConfig(steps=0),
}

PARITY_ARCHS = ("stablelm-1.6b", "qwen1.5-110b", "mistral-nemo-12b")
_PARITY_PAGE = 8


def run_train_serve_parity(archs=PARITY_ARCHS,
                           page_size: int = _PARITY_PAGE) -> Dict:
    """Train≡serve logits parity as a conformance cell.

    For each (reduced) registry arch: run the training-side ``forward`` in
    serve-canonical mode (``canonical_reductions=page_size``, see
    :mod:`repro.dist.fold`) over a fixed prompt set, and the paged
    ``ContinuousEngine`` with ``capture_prefill_logits`` over the same
    prompts (chunked prefill at the same page size).  The two per-prompt
    logit stacks are digested with :func:`repro.verify.digest.leaf_digest`;
    the cell is conformant iff every arch's train/serve digests match —
    i.e. prefill serving *is* the training forward, bit for bit.
    """
    from repro.models import transformer as T
    from repro.serve.engine import ContinuousEngine
    from repro.train import step as S
    from repro.verify.digest import combine_leaf_digests, leaf_digest

    prompt_lens = (5, 13, 32, 7)
    heads: Dict[str, str] = {}
    records: Dict[str, Dict[str, str]] = {}
    for arch in archs:
        cfg = registry.get(arch).reduced()
        params = T.init(cfg, jax.random.PRNGKey(0))
        rng = np.random.RandomState(0)
        prompts = [rng.randint(1, cfg.vocab, size=n).tolist()
                   for n in prompt_lens]
        eng = ContinuousEngine(cfg, params, n_slots=2, max_seq=64,
                               page_size=page_size, prefill_chunk=16,
                               capture_prefill_logits=True)
        for i, p in enumerate(prompts):
            eng.submit(p, req_id=i, max_new_tokens=1)
        eng.run()
        pcfg = cfg.replace(canonical_reductions=page_size)
        fwd = S.jit(pcfg, lambda pr, b, _c=pcfg: T.forward(pr, b, _c)[0])
        train_d, serve_d = {}, {}
        for i, p in enumerate(prompts):
            toks = jnp.asarray(np.asarray(p, np.int32)[None])
            logits = np.asarray(fwd(params, {"tokens": toks}))[0][: len(p)]
            train_d[f"req{i}"] = leaf_digest(logits.astype(np.float32))
            serve_d[f"req{i}"] = leaf_digest(
                eng.prefill_logits[i].astype(np.float32))
        heads[f"{arch}/train"] = combine_leaf_digests(train_d)
        heads[f"{arch}/serve"] = combine_leaf_digests(serve_d)
        records[arch] = {"train": train_d, "serve": serve_d}
    conformant = all(heads[f"{a}/train"] == heads[f"{a}/serve"]
                     for a in archs)
    return {
        "cell": "train_serve_parity",
        "config": {"archs": list(archs), "page_size": page_size,
                   "prompt_lens": list(prompt_lens)},
        "heads": heads,
        "records": records,
        "conformant": conformant,
        "first_divergence": {} if conformant else {
            a: [r for r in records[a]["train"]
                if records[a]["train"][r] != records[a]["serve"][r]]
            for a in archs
            if heads[f"{a}/train"] != heads[f"{a}/serve"]},
    }


def run_cell(name: str, *, crash_at: int = 2,
             scenarios=("straight", "resume", "elastic")) -> Dict:
    """Run one matrix cell through the requested scenarios; returns a report
    dict with chain records and a ``conformant`` verdict."""
    if name == "train_serve_parity":
        return run_train_serve_parity()
    lc = MATRIX[name]
    chains: Dict[str, DigestChain] = {}
    if "straight" in scenarios:
        chains["straight"] = run_straight(lc)
    with tempfile.TemporaryDirectory() as d:
        if "resume" in scenarios:
            chains["resume"] = run_with_crash_resume(
                lc, os.path.join(d, "resume"), crash_at)
        if "elastic" in scenarios:
            chains["elastic"] = run_elastic_reshard(
                lc, os.path.join(d, "elastic"), crash_at)
    heads = {k: c.head for k, c in chains.items()}
    ref = next(iter(chains.values()))
    divergences = {k: c.first_divergence(ref) for k, c in chains.items()}
    return {
        "cell": name,
        "config": dataclasses.asdict(lc),
        "heads": heads,
        "records": {k: c.records for k, c in chains.items()},
        "stream_head": stream_chain(lc).head,
        "conformant": len(set(heads.values())) == 1,
        "first_divergence": {k: v for k, v in divergences.items()
                             if v is not None},
    }


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--cells", default=",".join(MATRIX),
                    help="comma-separated MATRIX cell names")
    ap.add_argument("--scenarios", default="straight,resume,elastic")
    ap.add_argument("--crash-at", type=int, default=2)
    ap.add_argument("--out", default=None,
                    help="write the conformance JSON here (CI artifact)")
    args = ap.parse_args(argv)

    scenarios = tuple(args.scenarios.split(","))
    reports = [run_cell(c, crash_at=args.crash_at, scenarios=scenarios)
               for c in args.cells.split(",")]
    ok = all(r["conformant"] for r in reports)
    doc = {"n_devices": len(jax.devices()), "conformant": ok,
           "cells": reports}
    text = json.dumps(doc, indent=2)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text)
    for r in reports:
        status = "OK " if r["conformant"] else "FAIL"
        print(f"[{status}] {r['cell']}: " +
              " ".join(f"{k}={v[:12]}" for k, v in r["heads"].items()))
    print("conformant" if ok else "NON-CONFORMANT")
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
