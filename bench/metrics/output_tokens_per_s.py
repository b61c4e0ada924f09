"""Output tokens delivered inside the window, of finished requests or not,
over the window's seconds."""

def read(rec):
    w0, w1 = rec.window
    n = sum(w0 <= t < w1 for r in rec.records for t in r.tokens)
    return n / (w1 - w0)
