"""client: gap between consecutive output tokens, 90th percentile in ms.

Every gap between two consecutive tokens of one request, pooled over the
requests, where both tokens became visible inside the window (tokens are
stamped when ``Engine.step()`` returns)."""
import stats


def read(ctx):
    w = ctx.window
    gaps = sorted(stats.inter_token_ms([r.as_dict() for r in w.records],
                                       w.w0, w.w1))
    return stats.nearest_rank(gaps, 90) if gaps else None
