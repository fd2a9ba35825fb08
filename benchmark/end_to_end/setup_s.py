"""Start of ``run.py`` to the start of the window: the server's start,
every compile or cache load, the warm-up until the view's first window
has closed, every statement of the cell once."""


def read(window):
    return window["setup_s"]
