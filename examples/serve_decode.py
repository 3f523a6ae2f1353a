"""Serve the fine-tuned global model: batched prefill + decode.

    PYTHONPATH=src python examples/serve_decode.py

Fine-tunes briefly, extracts the aggregated global adapters (paper b4),
then runs the serving path: one prefill over the prompt batch and a
greedy decode loop against the KV cache.
"""

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import reduced
from repro.configs import get_config
from repro.core.system import SplitFTSystem, SystemConfig

arch = reduced(get_config("gpt2-small"), layers=4, d_model=64,
               vocab=2048, seq_len=64, batch=4)
arch = arch.replace(train=dataclasses.replace(
    arch.train, lr_client=3e-3, lr_server=3e-3))

# 1) fine-tune a few rounds
system = SplitFTSystem(arch, SystemConfig(num_samples=200,
                                          eval_samples=32), seed=0)
system.run(10, log_every=0)
params, adapters = system.serve_model()
model = system.model
print("fine-tuned; serving global model "
      f"(cuts were {np.asarray(system.state['cuts']).tolist()})")

# 2) serve: prefill a prompt batch, then greedy decode
B, PROMPT, GEN = 4, 24, 16
key = jax.random.PRNGKey(7)
prompt = jax.random.randint(key, (B, PROMPT), 3, arch.model.vocab_size)
cache = model.init_cache((B,), PROMPT + GEN)

prefill = jax.jit(lambda p, a, b, c: model.prefill(p, a, b, c))
decode = jax.jit(lambda p, a, t, c: model.decode_step(p, a, t, c))

t0 = time.time()
logits, cache = prefill(params, adapters, {"tokens": prompt}, cache)
nxt = jnp.argmax(logits[:, -1], -1)[:, None]
generated = [np.asarray(nxt)]
for _ in range(GEN - 1):
    logits, cache = decode(params, adapters, nxt, cache)
    nxt = jnp.argmax(logits[:, -1], -1)[:, None]
    generated.append(np.asarray(nxt))
jax.block_until_ready(nxt)
dt = time.time() - t0

out = np.concatenate(generated, axis=1)
print(f"prefill {B}x{PROMPT} + {GEN} decode steps in {dt:.2f}s")
for row in range(min(B, 2)):
    print(f"  seq {row}: {out[row].tolist()}")
