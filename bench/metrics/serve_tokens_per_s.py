"""Generated tokens that reached the host in the window, over the window
(host clock).  A batch cut by the window's end counts what it delivered."""


def read(rec):
    return rec.tokens / rec.window_s
