"""Seconds of compiles (persistent-cache loads included) during set-up,
from the checker's telemetry hub."""


def read(ctx):
    return ctx.setup_compile_s
