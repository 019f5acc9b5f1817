"""Host milliseconds per ``predict`` call in the program's
``nncg.to_device`` spans: the numpy input made a device buffer and the
copy in issued. The relayout of the input runs on the runtime's threads
after that, and is read by ``host_out_ms``."""
from bench.spans import TO_DEVICE, span_ms


def read(ctx):
    return span_ms(ctx, TO_DEVICE)
