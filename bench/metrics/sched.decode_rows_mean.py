"""scheduler: requests in each decode call, mean over the window's calls
(counted from the tokens each step delivered)."""


def read(ctx):
    rows = ctx.window.decode_rows
    return sum(rows) / len(rows) if rows else None
