"""program_idle: share of the traced window (%) in which the device is
idle while the host is inside one of the program's ``repro/`` host spans
(``repro/solve``, ``repro/update_coefficients``: argument handling and
dispatch), on the trace's clock."""
import scopes


def read(ctx):
    t = scopes.for_run(ctx)
    idle = None if t is None else scopes.program_idle_s(t)
    if idle is None:
        return None
    lo, hi = t.window()
    return 100.0 * idle * 1e9 / (hi - lo)
