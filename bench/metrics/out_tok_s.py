"""Tokens generated in the window, first tokens included, over the wall
time of the window's ``serve()`` call (host clock)."""


def read(run):
    return run.serve.tokens_out / run.wall_s
