"""Device idle time per round during which the host was at work in the
program: each idle nanosecond of device 0 in the traced window goes to
the innermost program span open at that instant, on the device trace's
clock (chipbench/program_spans.py); the metric sums the parts that fell
in spans other than `splitft.wait.*`.  Standard error gets every part
and the remainder (idle inside a wait, or outside every program span),
which add up to the window's idle time as `idle_share.train` reads it."""

from chipbench import program_spans
from chipbench.harness import log


def read(ctx):
    parts = program_spans.idle_by_span(ctx)
    rounds = ctx["counters"].get("rounds")
    if parts is None or not rounds:
        return None
    tr = ctx["trace"]
    exposed = sum(t for n, t in parts.items()
                  if n != program_spans.OUTSIDE
                  and not program_spans.is_wait(n))
    rest = sum(parts.values()) - exposed
    idle = tr.window_s - tr.busy_s(0)
    log(f"exposed_host_ms.train: device idle {1e3 * idle:.3f} ms over "
        f"{rounds} rounds; per round: "
        + ", ".join(f"{n} {1e3 * t / rounds:.3f} ms"
                    for n, t in parts.most_common())
        + f"; remainder (waits and outside the program) "
        f"{1e3 * rest / rounds:.3f} ms; parts sum "
        f"{1e3 * sum(parts.values()):.3f} ms")
    return 1e3 * exposed / rounds
