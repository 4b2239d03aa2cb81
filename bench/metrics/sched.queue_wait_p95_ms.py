"""scheduler: submit-to-admit wait, 95th percentile in ms.

Admission time is the start of the engine step whose number the request
records as ``admit_step``; a request due in the window and not admitted
by its end counts as its wait so far, above every admitted one."""
import stats


def read(ctx):
    w = ctx.window
    done, waiting = [], []
    for r in w.records:
        if not w.w0 <= r.due < w.w1:
            continue
        step = ctx.requests[r.rid].admit_step
        k = None if step is None else step - w.step0
        if k is not None and 0 <= k < len(w.step_start) \
                and w.step_start[k] <= w.w1:
            done.append(max(w.step_start[k] - r.sent, 0.0) * 1e3)
        else:
            waiting.append((w.w1 - r.sent) * 1e3)
    if not done and not waiting:
        return None
    return stats.censored_percentile(done, waiting, 95)
