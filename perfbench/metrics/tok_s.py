"""Output tokens delivered to handles inside the window, per second of
window."""


def read(run):
    n = run.tokens_in_window()
    return n / run.window_s if n else None
