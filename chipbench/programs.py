"""Which trace events belong to which program or kernel.

Kernels are found by the names `chip_smoke.py` finds in the compiled
HLO: the Pallas wrapper's name rides in each custom call's metadata.
The training round and the C3 evaluation step are found by their
modules' stable names, `jit_round_step` and `jit_c3_eval_step` (the
jitted functions `round_step` and `c3_eval_step`); per round each runs
once.
"""

from __future__ import annotations

FLASH_FWD = "flash_attention_pallas"
FLASH_BWD = "flash_attention_bwd_pallas"
DECODE_PAGED = "decode_attention_paged_pallas"
PREFILL_MODULE = "_prefill_raw"
DECODE_MODULE = "_decode_raw"
ROUND_MODULE = "jit_round_step"
C3_MODULE = "jit_c3_eval_step"
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
    """(round seconds, C3 evaluation seconds): the runs of the modules
    named ROUND_MODULE and C3_MODULE inside the window on one device;
    None where absent."""
    tot = {}
    for n, s, d in tr.modules[dev]:
        base = n.split("(", 1)[0]
        if base in (ROUND_MODULE, C3_MODULE) and s >= tr.window[0] \
                and s + d <= tr.window[1]:
            tot[base] = tot.get(base, 0.0) + d * 1e-9
    return tot.get(ROUND_MODULE), tot.get(C3_MODULE)


def module_named(name):
    return lambda n: name in n.split("(", 1)[0]
