"""tokens_s: output tokens read off the wire inside the window, over the
window's length."""
from bench import window


def read(run):
    return window.tokens_in_window(run) / run.seconds
