"""client: time to first token, 90th percentile in ms.

From each request's due time to its first token, over every request due
in the window; one with no first token by the window's end ranks above
every other and reads its wait so far.  Above the knee the queue grows
all through the window, so this tail swings with the smallest change
and stands here, beside the cell's tokens/s, not as an end-to-end
metric."""
import stats


def read(ctx):
    w = ctx.window
    recs = [r.as_dict() for r in w.records]
    done, waiting = stats.ttft_ms(recs, w.w0, w.w1)
    if not done and not waiting:
        return None
    return stats.censored_percentile(done, waiting, 90)
