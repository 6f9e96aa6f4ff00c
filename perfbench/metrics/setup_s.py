"""Process start to the window's first scheduled send (host clock)."""


def read(r):
    return r.setup_s
