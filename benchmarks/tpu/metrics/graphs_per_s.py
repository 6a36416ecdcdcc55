"""Graphs whose futures resolved inside the window, over the window's
seconds. Host clock, client side."""


def read(run):
    return run.completed / run.seconds if run.completed else None
