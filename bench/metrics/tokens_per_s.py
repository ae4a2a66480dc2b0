"""Output tokens emitted in the window, per second of the window."""


def read(rec):
    n = sum(1 for s in rec.served for t in s.stamps if rec.in_window(t))
    return n / rec.seconds
