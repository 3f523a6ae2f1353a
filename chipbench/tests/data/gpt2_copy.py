"""A family that is only a file: GPT-2's, under model_type gpt2_copy."""

from chipbench.families.gpt2 import (base_shapes, block, dims,  # noqa: F401
                                     draw, embed, head, program_sizes, tiny)
