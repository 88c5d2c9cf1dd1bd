"""Output tokens emitted inside the window, over the window's length.
Host clock; a token counts when ``gw.step()`` has returned it."""


def read(run):
    n = sum(1 for r in run.reqs for t in r.stamps if run.in_window(t))
    return n / run.window_s
