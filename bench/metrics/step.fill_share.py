"""jitted steps: real tokens fed over padded token slots, %.

From the stats of the window's ``engine.*dispatch`` spans: a prefill
chunk feeds ``tokens`` of its padded ``chunk``, a decode call ``rows``
of its ``bucket``; (sum of tokens + sum of rows) / (sum of chunks + sum
of buckets) (``engine_spans.fill_share``)."""
import engine_spans


def read(ctx):
    s = engine_spans.spans(ctx)
    return engine_spans.fill_share(s) if s else None
