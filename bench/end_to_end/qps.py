"""Requests answered inside the window, per second of the window
(closed-backlog cells)."""


def read(run):
    if run.loop != "backlog":
        return None
    return len(run.completed) / run.window_s
