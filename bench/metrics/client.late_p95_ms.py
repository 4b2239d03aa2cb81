"""client: how late the generator sent, 95th percentile in ms.

Send time minus due time, on the client's clock, over the requests due
in the window.  A late generator offers less load than the cell's rate:
read it before reading a fast TTFT."""
import stats


def read(ctx):
    w = ctx.window
    late = sorted((r.sent - r.due) * 1e3 for r in w.records
                  if w.w0 <= r.due < w.w1)
    return stats.nearest_rank(late, 95) if late else None
