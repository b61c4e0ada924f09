"""Seconds from process start to the first due arrival of the window:
imports, weights, compilation or cache loads, warm-up and the lead-in."""

def read(rec):
    return rec.setup_s
