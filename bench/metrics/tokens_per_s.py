"""Positions of the valid rows of every round the window ran, over the
window's wall seconds (host clock): each round's fetch, dispatch, combine
and update included."""


def read(run):
    return run.tokens / run.window_s if run.rounds else None
