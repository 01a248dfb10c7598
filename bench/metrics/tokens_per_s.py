"""Tokens of the steps completed in the window, over the time from the
window's start to the end of its last step (host clock)."""


def read(run):
    return run.tokens / run.window_s if run.window_s > 0 else None
