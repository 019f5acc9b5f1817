"""Milliseconds per ``predict`` call of the program's ``nncg.to_host``
spans in which the device ran nothing: the rest of the copy in and its
relayout, the completion signalled back and the copy back
(``bench/spans.py``)."""
from bench.spans import host_out_ms


def read(ctx):
    return host_out_ms(ctx)
