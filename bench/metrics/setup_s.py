"""Seconds from process start to the window's first timed call:
imports, TPU start-up, weights, frames, warming every shape the cell
uses (compiles or cache reads) and, for a server, its workers."""


def read(ctx):
    return ctx.setup_s
