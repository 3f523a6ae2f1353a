import os

# Kernel tests opt into Pallas interpret mode per-module via the
# REPRO_PALLAS_INTERPRET env var; everything else runs the jnp reference
# paths on the single CPU device.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
