"""setup_compile_s: backend compile and persistent-cache load seconds
inside the program's ``repro/setup`` host span (``GAMGSolver``
construction) in this run, from the program's own host-span records."""


def read(ctx):
    try:
        from repro.obs.trace import host_spans
    except ImportError:
        return None
    run_start = ctx.window[0] - ctx.setup_s
    recs = [r for r in host_spans()
            if r.name == "setup" and r.start >= run_start]
    return sum(r.compile_s for r in recs) if recs else None
