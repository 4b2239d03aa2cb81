"""scheduler: submit-to-admit wait as the engine stamps it, 95th
percentile in ms.

``Request.admit_s - Request.submit_s`` (both stamped by the engine and
its scheduler) over the requests due in the window; a request not
admitted by the window's end counts its wait so far, above every
admitted one, as in ``sched.queue_wait_p95_ms``.  A program that does
not stamp ``admit_s`` reads nothing."""
import stats


def read(ctx):
    w = ctx.window
    done, waiting = [], []
    for r in w.records:
        if not w.w0 <= r.due < w.w1:
            continue
        req = ctx.requests[r.rid]
        if not hasattr(req, "admit_s") or req.submit_s is None:
            return None
        if req.admit_s is not None and req.admit_s <= w.w1:
            done.append(max(req.admit_s - req.submit_s, 0.0) * 1e3)
        else:
            waiting.append((w.w1 - req.submit_s) * 1e3)
    if not done and not waiting:
        return None
    return stats.censored_percentile(done, waiting, 95)
