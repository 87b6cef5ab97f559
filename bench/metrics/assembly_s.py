"""assembly_s: host clock around the program's device assembly in set-up
(``repro.fem.assemble``), blocked until the operator is on the device."""


def read(ctx):
    d = ctx.spans.durations("assembly")
    return sum(d) if d else None
