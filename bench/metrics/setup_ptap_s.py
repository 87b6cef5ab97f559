"""setup_ptap_s: host seconds of the set-up's numeric Galerkin products,
the program's ``repro/setup/level*/ptap_numeric`` host spans (each waits
for its own outputs) in this run, from the program's own host-span
records."""
import re

PHASE = re.compile(r"setup/level\d+/ptap_numeric")


def read(ctx):
    try:
        from repro.obs.trace import host_spans
    except ImportError:
        return None
    run_start = ctx.window[0] - ctx.setup_s
    d = [r.seconds for r in host_spans()
         if PHASE.fullmatch(r.name) and r.start >= run_start]
    return sum(d) if d else None
