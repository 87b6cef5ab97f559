"""setup_s: process start to window start (loading, set-up, compiling,
warming up), host clock."""


def read(ctx):
    return ctx.setup_s
