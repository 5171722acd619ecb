"""Share of the serve loop's host time, over the traced passes, that it
spends on its own work rather than blocked on device results, in percent:
``(pass_s - readback_s) / pass_s`` from the engine counters the
``serve/pass`` spans carry (``scopes.py``). The device waits on the host
for this share of each pass. None where the program keeps no such
counters."""

from chipbench import scopes


def read(ctx):
    if ctx.trace is None:
        return None
    c = scopes.counters_of(ctx)
    if not c or c.get("pass_s", 0) <= 0:
        return None
    return 100.0 * (c["pass_s"] - c["readback_s"]) / c["pass_s"]
