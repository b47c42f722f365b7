"""Architecture registry: the 10 assigned configs + the paper's own benchmark
config. ``get(name)`` returns a ModelConfig; ``--arch <id>`` in the launchers
resolves through here. Sources/verification tiers are noted per config file."""
from __future__ import annotations

import importlib

ARCHS = [
    "stablelm_1_6b",
    "qwen1_5_110b",
    "nemotron_4_15b",
    "mistral_nemo_12b",
    "xlstm_350m",
    "internvl2_1b",
    "phi3_5_moe",
    "llama4_scout",
    "jamba_1_5_large",
    "whisper_base",
]

ALIASES = {
    "stablelm-1.6b": "stablelm_1_6b",
    "qwen1.5-110b": "qwen1_5_110b",
    "nemotron-4-15b": "nemotron_4_15b",
    "mistral-nemo-12b": "mistral_nemo_12b",
    "xlstm-350m": "xlstm_350m",
    "internvl2-1b": "internvl2_1b",
    "phi3.5-moe-42b-a6.6b": "phi3_5_moe",
    "llama4-scout-17b-a16e": "llama4_scout",
    "jamba-1.5-large-398b": "jamba_1_5_large",
    "whisper-base": "whisper_base",
    "dash-paper": "dash_paper",
    "mellum2-12b-a2.5b": "mellum2_12b_a2_5b",
}


# Speculative-decoding drafter pairing (repro.serve.spec): for each
# paged-servable target, the registry arch that drafts for it — the smallest
# attention-only decoder.  ``None`` means self-draft (the target drafts for
# itself; acceptance is 1.0 by construction).  The engine validates the one
# hard compatibility rule at construction: drafter and target must share a
# vocabulary (true across ``reduced()`` configs, which pin vocab=512; at full
# scale a vocab-matched drafter checkpoint is required).
DRAFTERS = {
    "stablelm_1_6b": None,
    "qwen1_5_110b": "stablelm_1_6b",
    "nemotron_4_15b": "stablelm_1_6b",
    "mistral_nemo_12b": "stablelm_1_6b",
}


def get(name: str):
    mod_name = ALIASES.get(name, name.replace("-", "_").replace(".", "_"))
    mod = importlib.import_module(f"repro.configs.{mod_name}")
    return mod.CONFIG


def drafter_for(name: str):
    """Canonical drafter arch name for ``name`` (aliases resolve), or None
    for self-draft.  Raises KeyError for targets the paged serving path
    (and therefore speculation) does not cover."""
    canon = ALIASES.get(name, name.replace("-", "_").replace(".", "_"))
    if canon not in DRAFTERS:
        raise KeyError(
            f"{name!r} has no drafter pairing: speculative serving covers "
            f"the paged-servable archs {sorted(DRAFTERS)}")
    return DRAFTERS[canon]


def all_arch_names():
    return list(ARCHS)
