"""Public wrapper for the SSD scan.

Forward: Pallas kernel on TPU / interpret mode; chunked jnp oracle
otherwise.  Backward: jnp chunked path under custom_vjp (the chunked
formulation is scan-of-matmuls, which AD reverses efficiently; a dedicated
backward kernel is a §Perf extension).
"""

from __future__ import annotations

import functools
import os

import jax

from repro.kernels.ssd_scan import ref
from repro.kernels.ssd_scan.kernel import ssd_scan_pallas


def _use_pallas() -> bool:
    if os.environ.get("REPRO_PALLAS_INTERPRET") == "1":
        return True
    return jax.default_backend() == "tpu"


@functools.lru_cache(maxsize=None)
def _make_ssd(chunk: int):
    @jax.custom_vjp
    def scan(x, dt, a, bm, c):
        interp = os.environ.get("REPRO_PALLAS_INTERPRET") == "1"
        return ssd_scan_pallas(x, dt, a, bm, c, chunk=chunk,
                               interpret=interp)

    def fwd(x, dt, a, bm, c):
        return scan(x, dt, a, bm, c), (x, dt, a, bm, c)

    def bwd(res, g):
        x, dt, a, bm, c = res
        def f(x, dt, a, bm, c):
            return ref.ssd_chunked(x, dt, a, bm, c, chunk=chunk)
        _, vjp = jax.vjp(f, x, dt, a, bm, c)
        return vjp(g)

    scan.defvjp(fwd, bwd)
    return scan


def ssd_scan(x, dt, a, bm, c, *, chunk: int = 256):
    """x (B,S,H,P); dt (B,S,H); a (H,); bm/c (B,S,G,N) -> y (B,S,H,P)."""
    chunk = min(chunk, x.shape[1])
    if not _use_pallas():
        return ref.ssd_chunked(x, dt, a, bm, c, chunk=chunk)
    return _make_ssd(chunk)(x, dt, a, bm, c)
