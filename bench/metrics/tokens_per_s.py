"""Tokens trained by all chips in the steps that completed in the window,
over the time from the window's first dispatch to its last completed
``block_until_ready`` (host clock; batch building included)."""


def read(run):
    return run.window_tokens / run.window_s
