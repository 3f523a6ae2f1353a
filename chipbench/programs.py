"""Which trace events belong to which program or kernel.

Kernels are found by the names `chip_smoke.py` finds in the compiled
HLO: the Pallas wrapper's name rides in each custom call's metadata.
The training round and the C3 evaluation step are both jitted functions
named `step`, so their modules are both `jit_step(<fingerprint>)`; per
round each runs once, and the round (forward and backward) takes longer
than the evaluation (forward only).
"""

from __future__ import annotations

FLASH_FWD = "flash_attention_pallas"
FLASH_BWD = "flash_attention_bwd_pallas"
DECODE_PAGED = "decode_attention_paged_pallas"
PREFILL_MODULE = "_prefill_raw"
DECODE_MODULE = "_decode_raw"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")


def is_kernel(name):
    """Match on an op's name and its metadata (see trace.Trace)."""
    return lambda label: name in label


def is_flash_fwd(label):
    return FLASH_FWD in label and FLASH_BWD not in label


def is_flash_bwd(label):
    return FLASH_BWD in label


def is_collective(label):
    head = label.split(" ", 1)[0]
    return any(head.startswith(c) for c in COLLECTIVES)


def split_step_modules(tr, dev=0):
    """(round seconds, eval seconds) of the `step` module runs inside the
    window on one device: of the two compiled `jit_step` programs the
    one with more device time is the round; None where absent."""
    tot = {}
    for n, s, d in tr.modules[dev]:
        if _step_name(n) and s >= tr.window[0] and s + d <= tr.window[1]:
            tot[n] = tot.get(n, 0.0) + d * 1e-9
    ranked = sorted(tot.values(), reverse=True)
    return (ranked[0] if ranked else None,
            ranked[1] if len(ranked) > 1 else None)


def _step_name(n):
    base = n.split("(", 1)[0]
    return base in ("jit_step", "step") or base.endswith("_step")


def module_named(name):
    return lambda n: name in n.split("(", 1)[0]
