"""Host milliseconds per ``predict`` call in the program's
``nncg.launch`` spans: jit dispatch and the enqueue of the program."""
from bench.spans import LAUNCH, span_ms


def read(ctx):
    return span_ms(ctx, LAUNCH)
