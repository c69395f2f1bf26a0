"""Process start up to the first timed round: imports, data, trace, plan,
trainer, compilation or cache loads, and the warm-up rounds."""


def read(ctx):
    return ctx.setup_s
