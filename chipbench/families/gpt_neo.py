"""GPT-Neo (`model_type` gpt_neo): the GPT block of gpt.py under
GPT-Neo's keys, with global and windowed layers as `attention_types`
lays them out."""

from chipbench.families import gpt
from chipbench.families.gpt import (base_shapes, block, draw,  # noqa: F401
                                    embed, head, program_sizes)


def dims(cfg):
    d = cfg["hidden_size"]
    kinds = [k for pattern, reps in cfg["attention_types"]
             for _ in range(reps) for k in pattern]
    return gpt.make_dims(
        cfg, d=d, layers=cfg["num_layers"], heads=cfg["num_heads"],
        d_ff=cfg["intermediate_size"] or 4 * d,
        positions=cfg["max_position_embeddings"],
        windows=[cfg["window_size"] if k == "local" else 0 for k in kinds])


def tiny(cfg):
    """The program's `--reduced` sizes, one global and one window-32 layer."""
    return dict(cfg, num_layers=2, hidden_size=64, num_heads=4,
                intermediate_size=256, vocab_size=512,
                max_position_embeddings=256,
                attention_types=[[["global", "local"], 1]], window_size=32)
