"""state pools: share of prompt tokens served from the prefix cache, %.

Over the requests admitted in the window: prompt tokens the engine
skipped at admission (``skipped_prefill``) over all their prompt tokens."""


def read(ctx):
    w = ctx.window
    skipped = total = 0
    for r in w.records:
        req = ctx.requests[r.rid]
        k = None if req.admit_step is None else req.admit_step - w.step0
        if k is None or not 0 <= k < len(w.step_start) \
                or not w.w0 <= w.step_start[k] < w.w1:
            continue
        skipped += req.skipped_prefill
        total += r.prompt_len
    return 100.0 * skipped / total if total else None
