"""GPT-2 (`model_type` gpt2): the GPT block of gpt.py under GPT-2's keys."""

from chipbench.families import gpt
from chipbench.families.gpt import (base_shapes, block, draw,  # noqa: F401
                                    embed, head, program_sizes)


def dims(cfg):
    d, L = cfg["n_embd"], cfg["n_layer"]
    return gpt.make_dims(cfg, d=d, layers=L, heads=cfg["n_head"],
                         d_ff=cfg["n_inner"] or 4 * d,
                         positions=cfg["n_positions"], windows=[0] * L)


def tiny(cfg):
    """The program's `--reduced` sizes: 2 layers, width 64, vocabulary 512."""
    return dict(cfg, n_layer=2, n_embd=64, n_head=4, n_inner=256,
                vocab_size=512, n_positions=256, n_ctx=256)
