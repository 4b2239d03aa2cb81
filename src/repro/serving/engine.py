"""Event-driven continuous-batching inference engine.

One ``Engine.step()`` = one scheduler decision + at most one jitted
chunked-prefill call + one jitted decode call over every running
sequence.  Requests are admitted and retired PER STEP, so new traffic
joins a running batch without draining it (continuous batching).

Compile discipline: the decode batch is padded to power-of-two buckets
(at most log2(max_batch)+1 shapes) and prefill always runs at the fixed
(1, prefill_chunk) shape, so steady-state serving never re-jits.  The
mixer-state pools are donated into every call — XLA updates the touched
blocks/slots in place instead of double-buffering the whole cache.

Token selection happens INSIDE the jitted calls (serving/sampling.py):
each request carries SamplingParams (temperature / top-k / top-p /
seed / stop tokens) and the PRNG key for the token at sequence index i
is fold_in(PRNGKey(seed), i) — deterministic across bucket padding,
preemption, and swap-in by construction.  A stop token finishes the
request at the step it is emitted, releasing its blocks immediately.

Speculative decoding (``spec_k > 0``) drafts tokens by prompt-lookup
(n-gram match against the request's own prompt+output — no second
model) and verifies the whole draft in ONE prefill-shaped forward per
step: on the paper's batch-1 photonic pipeline a k-token verify costs
one pipeline fill plus k bottleneck-stage intervals, far less than k
sequential tokens, which is exactly the modeled speedup the cost model
reports.  Rejected suffixes roll back per layout: block/ring tables
rewind by committing only the accepted length (stale writes are masked
by per-row kv_len / ring positions), recurrent SSM slots restore a
pre-verify snapshot and re-advance by the accepted prefix.  Because
sampling is a pure function of (seed, position), the verified stream
is token-identical to non-speculative decoding at ANY temperature.

With cfg.precision == "bnn" every projection runs the packed
XNOR-popcount GEMM — the paper's inference mode — and the attached
PhotonicCostModel reports what the modeled OXBNN accelerator would
sustain on the same token stream, next to host wall-clock.
"""
from __future__ import annotations

import math
import time
import warnings
from dataclasses import asdict, dataclass

import jax.numpy as jnp
import numpy as np

from repro.kernels import ops as kops
from repro.serving import roles as R
from repro.serving.block_cache import MixerStateCache
from repro.serving.cost_model import PhotonicCostModel
from repro.serving.request import Request, State
from repro.serving.sampling import (SamplingParams, prompt_lookup_draft,
                                    sampling_rows)
from repro.serving.scheduler import Scheduler, SchedulerConfig
from repro.serving.tracing import Tracer


def nearest_rank(sorted_vals, p: float) -> float:
    """Nearest-rank percentile over an ascending sample: the smallest
    value with at least p% of the sample at or below it — 0-indexed
    ``ceil(p/100 * n) - 1``.  (``int(p/100 * n)`` biases p50 high on
    even n and reads p99 as the max for n = 100.)"""
    if not len(sorted_vals):
        return float("nan")
    n = len(sorted_vals)
    idx = max(math.ceil(p / 100 * n) - 1, 0)
    return sorted_vals[min(idx, n - 1)]


@dataclass(frozen=True)
class EngineConfig:
    block_size: int = 16
    num_blocks: int = 129            # 1 scratch + 128 allocatable
    max_batch: int = 8               # decode slots (padded to 2^k buckets)
    prefill_chunk: int = 16
    max_model_len: int = 256         # prompt + generation bound per request
    policy: str = "fcfs"             # fcfs | priority | slo
    max_tokens_in_flight: int = 0    # KV-footprint admission budget;
                                     # 0 = auto (2x the block pool's
                                     # token capacity — swap headroom
                                     # without unbounded admission)
    max_batched_tokens: int = 256
    tenants: str = ""                # slo-policy tenant spec in the
                                     # canonical "name=class:budget,..."
                                     # form (policy.tenants_arg)
    accelerator: str = "OXBNN_50"    # photonic cost-model target
    prefix_cache: bool = True        # content-addressed prompt block reuse
    preempt_policy: str = "swap"     # swap | recompute (fallback)
    num_slots: int = 0               # recurrent slots; 0 = max_batch + 1
    snapshot_slots: int = 0          # recurrent prefix-snapshot pool rows
                                     # (0 = 2 * max_batch; gated by
                                     # prefix_cache like the block index)
    spec_k: int = 0                  # speculative draft length (0 = off)
    spec_ngram: int = 3              # max n-gram for prompt-lookup drafts
    attn_impl: str = "auto"          # paged attention: pallas | xla | auto
    bnn_impl: str = "auto"           # packed BNN GEMM: pallas | xla | auto
    role: str = "mixed"              # worker role: mixed | prefill | decode
                                     # (serving/roles.py; prefill shards
                                     # hand completed prompts to a decode
                                     # peer via the ShardedEngine)
    link_gbps: float = 100.0         # modeled inter-shard link bandwidth
                                     # (prefill->decode handoff transfer)


class Engine:
    def __init__(self, params, cfg, ecfg: EngineConfig = EngineConfig()):
        self.params = params
        self.cfg = cfg
        self.ecfg = ecfg
        # one tracer threaded through scheduler + caches: disabled by
        # default (near-zero cost), start_trace() turns recording on.
        # Its span accumulators back the wall-time stats either way.
        self.tracer = Tracer()
        self.cache = MixerStateCache(
            cfg, num_blocks=ecfg.num_blocks,
            block_size=ecfg.block_size,
            max_model_len=ecfg.max_model_len,
            prefix_cache=ecfg.prefix_cache,
            num_slots=ecfg.num_slots or ecfg.max_batch + 1,
            prefill_chunk=ecfg.prefill_chunk,
            snapshot_slots=ecfg.snapshot_slots or 2 * ecfg.max_batch,
            tracer=self.tracer)
        # ring rollback safety: stale speculative writes must only ever
        # clobber positions already outside the attention window, which
        # the prefill-sized ring guarantees when the verify chunk is no
        # wider than a prefill chunk (k + 1 <= prefill_chunk)
        self._spec_k = (min(ecfg.spec_k, ecfg.prefill_chunk - 1)
                        if ecfg.spec_k > 0 else 0)
        # worker role (serving/roles.py): gates which plan rows run and
        # whether completed prefills park for peer handoff; a prefill
        # worker never drafts/verifies, so its spec budget is zero
        self.role = R.get_role(ecfg.role)
        if not self.role.runs_decode:
            self._spec_k = 0
        # admission token budget: 0 = derive from the block pool (2x
        # its token capacity — enough oversubscription for swap-based
        # preemption to matter, but no longer effectively unbounded).
        # Slot-only stacks have no block pool; their admission is
        # bounded by max_batch/num_slots instead.
        mtif = ecfg.max_tokens_in_flight
        if mtif == 0:
            a = self.cache.attn
            mtif = (2 * a.allocator.capacity * ecfg.block_size
                    if a is not None else 1 << 30)
        elif mtif >= 1 << 30 and self.cache.attn is not None:
            warnings.warn(
                "max_tokens_in_flight >= 1<<30: admission is "
                "KV-unconstrained — every queued request counts as "
                "admissible and block pressure is handled purely by "
                "preemption; pass max_tokens_in_flight=0 to derive a "
                "bound from the block pool", stacklevel=2)
        self.max_tokens_in_flight = mtif
        self.scheduler = Scheduler(
            SchedulerConfig(max_batch=ecfg.max_batch,
                            max_tokens_in_flight=mtif,
                            max_batched_tokens=ecfg.max_batched_tokens,
                            prefill_chunk=ecfg.prefill_chunk,
                            policy=ecfg.policy,
                            preempt_policy=ecfg.preempt_policy,
                            decode_cost=1 + self._spec_k,
                            tenants=ecfg.tenants),
            self.cache, tracer=self.tracer, role=self.role)
        # the fused Pallas chain never spills packed activations to
        # HBM; the XLA oracle prices the extra pack pass per GEMM
        self.cost_model = PhotonicCostModel(
            cfg, ecfg.accelerator,
            fused_bnn=kops.resolve_impl(ecfg.bnn_impl) == "pallas",
            link_gbps=ecfg.link_gbps)
        self.requests: dict[int, Request] = {}
        self.step_count = 0
        self._next_rid = 0
        # set by ShardedEngine when this engine is one decode shard of
        # a data-axis group; traces/stats then carry per-shard fields
        self.shard: int | None = None
        self.n_shards: int = 1
        self._step_rec: dict | None = None   # per-step trace assembly
        self._decoded = 0
        self._prefilled = 0
        self._prefill_calls = 0          # chunked-prefill passes (cost model)
        self._max_concurrent = 0
        self._decode_calls = 0
        self._decode_rows = 0            # scheduled rows across decode calls
        self._decode_produced = 0        # tokens committed by decode calls
        # speculative counters
        self._spec_steps = 0
        self._spec_rows = 0              # per-row verify passes
        self._verify_tokens = 0          # fed tokens across verify calls
        self._spec_committed = 0         # tokens committed by verify steps
        self._draft_tokens = 0
        self._draft_accepted = 0
        self._spec_repairs = 0
        # scoring workload counters (teacher-forced prefill-only)
        self._score_tokens = 0           # scored prompt positions
        self._score_passes = 0           # chunked scoring prefill calls
        self._score_requests = 0         # finished scoring requests
        self._cancelled = 0
        # incremental token-commit callback (streaming front-end):
        # cb(rid, new_tokens, done) at every commit point — spec-decode
        # commits surface as bursts.  None = no streaming overhead.
        self.on_commit = None
        self._has_slots = self.cache.ssm is not None
        # prompts whose prefill completed on a hand-off role, awaiting
        # export to a decode peer (drained by ShardedEngine.step)
        self.handoff_ready: list[int] = []

        # jitted step closures, built per role (serving/roles.py): a
        # prefill worker only compiles the prefill graph
        fns = R.build_step_fns(cfg, ecfg, self.role,
                               ring=self.cache.ring_blocks > 0,
                               spec_k=self._spec_k)
        self._prefill_fn = fns.prefill
        self._decode_fn = fns.decode
        self._spec_fn = fns.spec
        self._repair_fn = fns.repair

    # ---------------------------------------------------------------- API

    def start_trace(self, path: str | None = None, *, ring: int = 4096,
                    capture_logits: bool = False) -> Tracer:
        """Turn structured tracing on: every step/request/span event
        goes to a bounded in-memory ring and (when ``path`` is given)
        streams to JSONL.  The leading meta record makes the trace
        self-describing — the replay driver and the Perfetto exporter
        need nothing else (see serving/tracing.py)."""
        self.tracer.open(path, ring=ring, capture_logits=capture_logits)
        self.tracer.meta(
            arch=self.cfg.name, accelerator=self.ecfg.accelerator,
            config=asdict(self.cfg), engine=asdict(self.ecfg),
            spec_k=self._spec_k, shard=self.shard,
            n_shards=self.n_shards, role=self.role.name,
            link_gbps=self.ecfg.link_gbps, t0=self.tracer.t0)
        return self.tracer

    def stop_trace(self):
        """Flush + close the trace stream (ring stays readable)."""
        self.tracer.close()

    def submit(self, prompt, max_new: int, *, priority: int = 0,
               arrival_s: float = 0.0,
               sampling: SamplingParams | None = None,
               rid: int | None = None, tenant: str = "default",
               slo_class: str = "", score: bool = False) -> int:
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if score:
            # scoring = chunked teacher-forced prefill only: no decode
            # loop, so there is no generation budget to reserve
            max_new = 0
            if prompt.size < 2:
                raise ValueError(
                    "scoring needs >= 2 prompt tokens (each scored "
                    "position conditions on at least one token)")
        if prompt.size + max_new > self.ecfg.max_model_len:
            raise ValueError(
                f"request needs {prompt.size + max_new} tokens > "
                f"max_model_len={self.ecfg.max_model_len}")
        if not self.cache.fits(prompt.size + max_new):
            raise ValueError(
                f"request needs {prompt.size + max_new} tokens of KV > "
                f"the whole block pool; raise num_blocks")
        if rid is None:
            rid = self._next_rid
        # keep local allocation clear of externally-assigned rids (the
        # ShardedEngine owns a global rid space across its shards)
        self._next_rid = max(self._next_rid, rid) + 1
        req = Request(rid, prompt, max_new, priority=priority,
                      arrival_s=arrival_s,
                      sampling=sampling or SamplingParams(),
                      tenant=tenant, slo_class=slo_class, score=score)
        req.submit_s = time.perf_counter()
        self.requests[rid] = req
        self.scheduler.submit(req, self.step_count)
        return rid

    def set_commit_callback(self, cb):
        """Install ``cb(rid, new_tokens, done)``, fired at every token
        commit: prefill first token, each plain decode token, and
        speculative commits as whole accepted bursts.  ``new_tokens``
        only ever contains tokens past the request's delivery watermark
        — recompute preemption regenerates an identical prefix (seed/
        position determinism), which is NOT re-delivered, so the
        concatenated stream is byte-identical to ``run()`` output."""
        self.on_commit = cb

    def _commit(self, req: Request, done: bool):
        if self.on_commit is None:
            return
        new = req.out[req.streamed:]
        if new or done:
            req.streamed = len(req.out)
            self.on_commit(req.rid, list(new), done)

    def cancel(self, rid: int) -> bool:
        """First-class cancellation.  Queued requests are dropped;
        running ones release their blocks/slots through the same cache
        paths preemption uses; swapped ones just drop their host
        buffers (``swap_out`` already freed the device blocks).  The
        request ends in the terminal CANCELLED state with a
        ``cancelled`` trace event — never counted as a ``swap_lost``
        or a preemption.  Returns False when rid is unknown or already
        terminal."""
        req = self.requests.get(rid)
        if req is None or req.state in (State.FINISHED, State.CANCELLED):
            return False
        sched = self.scheduler
        if req in sched.running:
            sched.running.remove(req)
            self.cache.release(req)
        elif req in sched.queue:
            sched.queue.remove(req)
            if req.state == State.SWAPPED:
                req.host_kv = None
                req.host_state = None
        if rid in self.handoff_ready:
            self.handoff_ready.remove(rid)
        req.state = State.CANCELLED
        req.finish_step = self.step_count
        req.finish_s = time.perf_counter()
        self._cancelled += 1
        sched._ev(self.step_count, "cancelled", rid,
                  generated=len(req.out))
        self._commit(req, True)
        return True

    def _counter_marks(self) -> tuple:
        """Cheap cache/scheduler counter snapshot — the step record
        reports per-step deltas (prefix/snapshot hits, preempt/swap
        actions).  Built only while tracing is enabled."""
        c, s = self.cache, self.scheduler
        a, m = c.attn, c.ssm
        return (a.prefix_hits if a is not None else 0,
                m.snap_hits if m is not None else 0,
                s.preempts, s.swap_losts, c.swap_outs, c.swap_ins)

    def step(self) -> bool:
        """One engine iteration; False when nothing was schedulable.

        Its phases are tracer spans (``schedule``, then per kind
        ``prepare`` / ``dispatch`` / ``sync`` / ``commit``) inside the
        root ``step`` span, each carrying this step's number."""
        step = self.step_count
        tr = self.tracer
        tr.step = step
        with tr.span("step") as root:
            if tr.enabled:
                self._step_rec = {}
                marks = self._counter_marks()
            with tr.span("schedule"):
                plan = self.scheduler.schedule(step)
            if plan.prefill is not None:
                self._run_prefill(step, plan.prefill, plan.prefill_tokens)
            # prefill-side preemption may have requeued planned decode rows
            decode = [r for r in plan.decode
                      if r.state == State.DECODE
                      and r in self.scheduler.running]
            if decode:
                if self._spec_k:
                    self._run_decode_spec(step, decode)
                else:
                    self._run_decode(step, decode)
            self.step_count += 1
        tr.step = self.step_count
        if tr.enabled:
            rec = self._step_rec
            self._step_rec = None
            delta = [b - a for a, b in zip(marks, self._counter_marks())]
            keys = ("prefix_hits", "snapshot_hits", "preempts",
                    "swap_losts", "swap_outs", "swap_ins")
            actions = {k: d for k, d in zip(keys, delta) if d}
            ev = {"type": "step", "step": step, "dur_s": root.dur,
                  "kind": "+".join(
                      k for k in ("prefill", "decode", "spec_verify")
                      if k in rec) or "idle",
                  "role": self.role.name}
            if self.shard is not None:
                ev["shard"] = self.shard
            ev.update(rec)
            if actions:
                ev["actions"] = actions
            tr.emit(ev)
        return plan.has_work

    def run(self) -> dict[int, np.ndarray]:
        """Drive until every submitted request finished; returns
        rid -> full token sequence (prompt + generated)."""
        while not self.scheduler.idle:
            if not self.step():
                stalls = self.scheduler.stall_reasons()
                detail = "; ".join(
                    f"rid={rid}[{state}]: {why}"
                    for rid, (state, why) in sorted(stalls.items()))
                raise RuntimeError(
                    "engine stalled with unschedulable requests — last "
                    f"defer/swap_lost reason per request: {detail}")
        return {rid: r.full_sequence() for rid, r in self.requests.items()
                if r.state == State.FINISHED}

    # ------------------------------------------- replay-curve feedback

    def apply_replay_curve(self, curve: dict) -> int:
        """Cap the speculative verify chunk at the modeled DWDM
        pipeline-fill break-even of a replayed ``decode_batch_curve``
        (serving/replay.py).  Beyond the break-even width, each extra
        verified token costs more than a sequential decode step on the
        modeled hardware, so drafting past it cannot win.  Lowers
        ``spec_k`` (never raises it — the ring-rollback bound still
        applies) and the scheduler's per-row decode budget charge.
        Returns the spec_k now in effect."""
        from repro.serving.replay import spec_chunk_cap
        cap = spec_chunk_cap(curve)
        if cap is not None and self._spec_k and cap - 1 < self._spec_k:
            self._spec_k = max(cap - 1, 0)
            self.scheduler.decode_cost = 1 + self._spec_k
        return self._spec_k

    # ------------------------------------------------- shard migration

    def export_request(self, rid: int, peer: "Engine | None" = None):
        """Detach a live request for migration to a peer shard.

        A running request with computed state is serialized through the
        content-hash swap path — against the PEER's indexes when one is
        given, so blocks/snapshots the destination already holds by
        hash never cross shards.  Queued and already-swapped requests
        move as-is (their host buffers are portable; re-adoption depth
        resolves against the destination at admission, degrading to
        swap_lost recompute if its chains are missing).  Returns the
        Request, no longer tracked by this engine."""
        req = self.requests.pop(rid)
        step = self.step_count
        if rid in self.handoff_ready:
            self.handoff_ready.remove(rid)
        if req in self.scheduler.running:
            self.scheduler.running.remove(req)
            if req.pos > 0:
                self.cache.swap_out(req, peer=peer.cache if peer else None)
                req.park_swapped()
            else:
                self.cache.release(req)
                req.reset_for_requeue()
        elif req in self.scheduler.queue:
            self.scheduler.queue.remove(req)
        self.scheduler._ev(step, "migrate_out", req.rid, pos=req.pos,
                           state=req.state.value)
        return req

    def adopt_request(self, req: Request, *, lost: bool = False):
        """Take over a migrated request (counterpart of
        ``export_request``).  ``lost=True`` = rescued from a dead shard
        with its device state gone: reset for recompute-from-scratch
        and surface the loss as ``swap_lost`` (scheduler.adopt)."""
        if lost:
            req.reset_for_requeue()
            req.transfer_steps = 0
            req.transfer_until_step = None
        if req.transfer_steps:
            # transfer-aware admission: the modeled link is still
            # streaming this request's state; the scheduler defers it
            # (reason=transfer_pending) until the arrival deadline,
            # overlapping the transfer with this shard's decode steps
            req.transfer_until_step = self.step_count + req.transfer_steps
        self.requests[req.rid] = req
        self._next_rid = max(self._next_rid, req.rid + 1)
        self.scheduler.adopt(req, self.step_count, lost=lost)

    # ------------------------------------------------------------ internals

    def _run_prefill(self, step: int, req: Request, chunk: int):
        tr = self.tracer
        cp = self.ecfg.prefill_chunk   # fixed padded shape (no re-jit)
        stats = {"rid": req.rid, "tokens": chunk, "chunk": cp}
        with tr.span("prefill.prepare", **stats):
            if not self.scheduler.grow_or_preempt(step, req,
                                                  req.pos + chunk):
                return                 # req itself was preempted
            # copy-on-write: never scatter into a block another owner
            # shares (the full-prefix-match case re-prefills its final
            # token here)
            for idx in self.cache.writable_indices(req.pos, chunk):
                if not self.scheduler.make_writable(step, req, idx):
                    return
            tokens = np.zeros((1, cp), np.int32)
            tokens[0, :chunk] = req.prompt[req.pos:req.pos + chunk]
            table = self.cache.table_rows([req], 1)
            slots = self.cache.slot_rows([req], 1)
            srows = sampling_rows([req], 1)
            args = (jnp.asarray(tokens), jnp.asarray(table),
                    jnp.asarray([req.pos], jnp.int32),
                    jnp.asarray([chunk], jnp.int32), jnp.asarray(slots),
                    *srows.as_args())
        with tr.span("prefill.dispatch", sampled=srows.sampled, **stats):
            tok, _logits, pools = self._prefill_fn(
                self.params, self.cache.pools, *args)
            self.cache.pools = pools
        done = req.pos + chunk == req.prompt_len
        if req.score or done:
            with tr.span("prefill.sync", **stats):
                if req.score:
                    # teacher-forced scoring: the chunk's logits rows
                    # predict prompt positions pos+1 .. pos+chunk (same
                    # capture path the tracer's capture_logits uses)
                    score_rows = np.asarray(_logits[0, :chunk], np.float32)
                else:
                    first = int(np.asarray(tok)[0])
        with tr.span("prefill.commit", **stats):
            if req.score:
                self._accumulate_score(req, score_rows, chunk)
                self._score_passes += 1
            req.pos += chunk
            self._prefilled += chunk
            self._prefill_calls += 1
            self.cache.register_prefix(req)
            self.scheduler._ev(step, "prefill", req.rid, tokens=chunk,
                               pos=req.pos)
            if self._step_rec is not None:
                info = {"rid": req.rid, "tokens": chunk, "pos": req.pos,
                        "prompt_len": req.prompt_len}
                if req.score:
                    info["score"] = True
                if self.tracer.capture_logits:
                    info["logits"] = np.asarray(
                        _logits[0, :chunk], np.float32).tolist()
                self._step_rec["prefill"] = info
            if not done:
                return
            if req.score:
                # scoring never decodes: the request finishes straight
                # out of its last prefill chunk, releasing its state
                req.first_token_step = step
                req.first_token_s = time.perf_counter()
                self._score_requests += 1
                self.scheduler.finish(step, req)
                req.finish_s = req.first_token_s
                self._commit(req, True)
                return
            req.out.append(first)
            req.state = State.DECODE
            req.first_token_step = step
            req.first_token_s = time.perf_counter()
            self._decoded += 1
            self.scheduler._ev(step, "first_token", req.rid)
            if req.done:
                self.scheduler.finish(step, req)
                req.finish_s = time.perf_counter()
            elif self.role.hands_off:
                # prefill worker: the prompt (and its first token) are
                # done here — park for export to a decode peer.  The
                # ShardedEngine drains this list right after the step
                # and streams the request over the swap-to-peer path.
                self.handoff_ready.append(req.rid)
                self.scheduler._ev(step, "handoff_ready", req.rid,
                                   pos=req.pos)
            self._commit(req, req.done)

    def _accumulate_score(self, req: Request, logits: np.ndarray,
                          chunk: int):
        """Append log p(prompt[pos+1+j] | prefix) for each scored row
        of the chunk (row j predicts position pos+j+1; the final row
        has no target inside the prompt)."""
        n = min(chunk, req.prompt_len - req.pos - 1)
        if n <= 0:
            return
        rows = logits[:n].astype(np.float64)
        mx = rows.max(axis=-1)
        lse = mx + np.log(np.exp(rows - mx[:, None]).sum(axis=-1))
        tgt = np.asarray(req.prompt[req.pos + 1:req.pos + 1 + n], np.int64)
        lp = rows[np.arange(n), tgt] - lse
        req.logprobs.extend(float(x) for x in lp)
        self._score_tokens += n

    @staticmethod
    def _bucket(n: int) -> int:
        b = 1
        while b < n:
            b <<= 1
        return b

    def _ready_rows(self, step: int, reqs: list[Request],
                    lookahead) -> list[Request]:
        """Grow + CoW every decodable row for ``lookahead(r)`` new cache
        positions, dropping rows that get preempted along the way."""
        ready: list[Request] = []
        for r in reqs:
            if r not in self.scheduler.running or r.state != State.DECODE:
                continue
            n_new = lookahead(r)
            if not self.scheduler.grow_or_preempt(step, r, r.pos + n_new):
                continue
            ok = True
            for idx in self.cache.writable_indices(r.pos, n_new):
                if not self.scheduler.make_writable(step, r, idx):
                    ok = False
                    break
            if ok:
                ready.append(r)
        # a later grow may have preempted an earlier 'ready' row
        return [r for r in ready
                if r in self.scheduler.running and r.state == State.DECODE]

    def _planned(self, reqs: list[Request]) -> dict:
        """Span stats of a decode call's planned rows and bucket (the
        prepare phase may still drop rows to preemption)."""
        return {"rows": len(reqs),
                "bucket": min(self._bucket(len(reqs)), self.ecfg.max_batch)}

    def _run_decode(self, step: int, reqs: list[Request]):
        tr = self.tracer
        with tr.span("decode.prepare", **self._planned(reqs)):
            ready = self._ready_rows(step, reqs, lambda r: 1)
            if not ready:
                return
            bucket = min(self._bucket(len(ready)), self.ecfg.max_batch)
            tokens = np.zeros((bucket, 1), np.int32)
            lengths = np.zeros(bucket, np.int32)
            active = np.zeros(bucket, bool)
            for i, r in enumerate(ready):
                tokens[i, 0] = r.last_token
                lengths[i] = r.pos
                active[i] = True
            table = self.cache.table_rows(ready, bucket)
            slots = self.cache.slot_rows(ready, bucket)
            srows = sampling_rows(ready, bucket)
            args = (jnp.asarray(tokens), jnp.asarray(table),
                    jnp.asarray(lengths), jnp.asarray(active),
                    jnp.asarray(slots), *srows.as_args())
        stats = {"rows": len(ready), "bucket": bucket}
        with tr.span("decode.dispatch", sampled=srows.sampled, **stats):
            next_tok, _dec_logits, pools = self._decode_fn(
                self.params, self.cache.pools, *args)
            self.cache.pools = pools
        with tr.span("decode.sync", **stats):
            next_tok = np.asarray(next_tok)
        with tr.span("decode.commit", **stats):
            self._max_concurrent = max(self._max_concurrent, len(ready))
            self._decode_calls += 1
            self._decode_rows += len(ready)
            self._decode_produced += len(ready)
            self.scheduler._ev(step, "decode", None,
                               rids=[r.rid for r in ready], batch=bucket)
            if self._step_rec is not None:
                info = {"rows": len(ready), "bucket": bucket,
                        "rids": [r.rid for r in ready],
                        "fed_tokens": len(ready), "committed": len(ready)}
                if self.tracer.capture_logits:
                    info["logits"] = np.asarray(
                        _dec_logits[:len(ready), -1], np.float32).tolist()
                self._step_rec["decode"] = info
            now = time.perf_counter()
            for i, r in enumerate(ready):
                if r.state is not State.DECODE:
                    continue    # cancelled mid-loop by a commit callback
                r.pos += 1
                r.out.append(int(next_tok[i]))
                self._decoded += 1
                if r.done:
                    self.scheduler.finish(step, r)
                    r.finish_s = now
                self._commit(r, r.done)

    # ------------------------------------------------- speculative decode

    def _run_decode_spec(self, step: int, reqs: list[Request]):
        """One verify step: draft by prompt lookup, score the whole
        draft in one multi-token forward, commit the accepted prefix
        plus the verifier's own next token, roll back the rest."""
        drafts: dict[int, np.ndarray] = {}

        def lookahead(r: Request) -> int:
            budget = min(self._spec_k, r.max_new - len(r.out) - 1)
            d = (prompt_lookup_draft(r.full_sequence(), budget,
                                     self.ecfg.spec_ngram)
                 if budget > 0 else np.empty(0, np.int32))
            drafts[r.rid] = d
            return len(d) + 1

        tr = self.tracer
        with tr.span("spec.prepare", **self._planned(reqs)):
            ready = self._ready_rows(step, reqs, lookahead)
            # nothing to verify: a chunk-wide forward would commit the
            # same single token per row at prefill-shaped cost — take
            # the (B, 1) decode path (capacity/CoW above already cover
            # one token, so the re-check inside is a no-op)
            plain = all(len(drafts[r.rid]) == 0 for r in ready)
            if ready and not plain:
                bucket = min(self._bucket(len(ready)), self.ecfg.max_batch)
                c = self._spec_k + 1
                tokens = np.zeros((bucket, c), np.int32)
                draft = np.zeros((bucket, c - 1), np.int32)
                n_valid = np.zeros(bucket, np.int32)
                lengths = np.zeros(bucket, np.int32)
                for i, r in enumerate(ready):
                    d = drafts[r.rid]
                    tokens[i, 0] = r.last_token
                    tokens[i, 1:1 + len(d)] = d
                    draft[i, :len(d)] = d
                    n_valid[i] = len(d) + 1
                    lengths[i] = r.pos
                table = self.cache.table_rows(ready, bucket)
                slots = self.cache.slot_rows(ready, bucket)
                srows = sampling_rows(ready, bucket)
                j_tokens, j_table, j_lengths, j_valid, j_slots = (
                    jnp.asarray(tokens), jnp.asarray(table),
                    jnp.asarray(lengths), jnp.asarray(n_valid),
                    jnp.asarray(slots))
                j_draft = jnp.asarray(draft)
        if not ready:
            return
        if plain:
            self._run_decode(step, ready)
            return
        fed = int(n_valid[:len(ready)].sum())
        stats = {"rows": len(ready), "bucket": bucket, "tokens": fed,
                 "chunk": bucket * c}
        with tr.span("spec.dispatch", sampled=srows.sampled, **stats):
            sampled, n_commit_d, pools, snaps = self._spec_fn(
                self.params, self.cache.pools, j_tokens, j_table,
                j_lengths, j_valid, j_slots, j_draft, *srows.as_args())
            self.cache.pools = pools
        with tr.span("spec.sync", **stats):
            n_commit = np.asarray(n_commit_d)
            sampled = np.asarray(sampled)
        # recurrent slots folded the FULL draft into their state; any
        # partial acceptance needs the snapshot-restore + re-advance
        if self._has_slots and bool(np.any(
                n_commit[:len(ready)] < n_valid[:len(ready)])):
            with tr.span("spec.dispatch", **stats):
                self.cache.pools = self._repair_fn(
                    self.params, self.cache.pools, j_tokens, j_table,
                    j_lengths, n_commit_d, j_slots, snaps)
            self._spec_repairs += 1
        with tr.span("spec.commit", **stats):
            self._commit_spec(step, ready, sampled, n_commit, n_valid,
                              bucket)

    def _commit_spec(self, step: int, ready: list[Request],
                     sampled: np.ndarray, n_commit: np.ndarray,
                     n_valid: np.ndarray, bucket: int):
        """Commit each row's accepted draft prefix plus the verifier's
        own token, and account the verify step."""
        self._max_concurrent = max(self._max_concurrent, len(ready))
        self._decode_calls += 1
        self._decode_rows += len(ready)
        self._spec_steps += 1
        self._spec_rows += len(ready)
        now = time.perf_counter()
        committed_total = 0
        for i, r in enumerate(ready):
            if r.state is not State.DECODE:
                continue    # cancelled mid-loop by a commit callback
            m = int(n_commit[i])
            self._verify_tokens += int(n_valid[i])
            self._draft_tokens += int(n_valid[i]) - 1
            committed = 0
            for jj in range(m):
                r.pos += 1
                r.out.append(int(sampled[i, jj]))
                self._decoded += 1
                committed += 1
                if r.done:      # stop/max_new mid-draft: finish here —
                    break       # the request's state is released anyway
            # credit only draft tokens that actually COMMITTED: a stop
            # landing mid-draft truncates the accepted prefix, and
            # counting the full m - 1 would inflate acceptance_rate
            # relative to the tokens the stream really contains (the
            # last committed token is the verifier's own bonus token
            # only when the whole accepted prefix made it in)
            self._draft_accepted += min(committed, m - 1)
            committed_total += committed
            if r.done:
                self.scheduler.finish(step, r)
                r.finish_s = now
            # the whole accepted burst surfaces as ONE commit — the
            # streaming contract for speculative decoding
            self._commit(r, r.done)
        self._spec_committed += committed_total
        self._decode_produced += committed_total
        self.scheduler._ev(step, "spec_decode", None,
                           rids=[r.rid for r in ready], batch=bucket,
                           drafted=int(n_valid[:len(ready)].sum())
                           - len(ready),
                           committed=committed_total)
        if self._step_rec is not None:
            self._step_rec["spec_verify"] = {
                "rows": len(ready), "bucket": bucket,
                "rids": [r.rid for r in ready],
                "fed": n_valid[:len(ready)].tolist(),
                "fed_tokens": int(n_valid[:len(ready)].sum()),
                "drafted": int(n_valid[:len(ready)].sum()) - len(ready),
                "accepted": int(np.minimum(
                    n_commit[:len(ready)] - 1,
                    n_valid[:len(ready)] - 1).clip(0).sum()),
                "committed": committed_total}

    # -------------------------------------------------------------- stats

    def reset_stats(self, *, flush_prefix: bool = False):
        """Zero the token/wall/cache counters without touching request
        or scheduler state — benches call this after jit warmup so the
        measured window starts from a clean slate."""
        self.tracer.reset_spans("step")
        self._decoded = self._prefilled = self._prefill_calls = 0
        self._max_concurrent = 0
        self._decode_calls = self._decode_rows = self._decode_produced = 0
        self._spec_steps = self._spec_rows = 0
        self._verify_tokens = self._spec_committed = 0
        self._draft_tokens = self._draft_accepted = 0
        self._spec_repairs = 0
        self._score_tokens = self._score_passes = 0
        self._score_requests = self._cancelled = 0
        self.cache.reset_stats(flush_prefix=flush_prefix)

    def stats(self) -> dict:
        finished = [r for r in self.requests.values()
                    if r.state == State.FINISHED]
        lat = sorted(r.finish_s - r.submit_s for r in finished
                     if r.finish_s is not None and r.submit_s is not None)
        c = self.cache
        prefix = c.prefix_section()
        # the span accumulator (serving/tracing.py) is the single
        # source of wall-time truth: the same number the emitted step
        # records sum to (asserted in tests/test_tracing.py)
        wall_s = self.tracer.span_total("step")
        return {
            "steps": self.step_count,
            "role": self.role.name,
            "finished": len(finished),
            "decoded_tokens": self._decoded,
            "prefill_tokens": self._prefilled,
            "wall_s": wall_s,
            # decode-only rate AND the all-computed-tokens rate: the
            # wall clock covers prefill too, so dividing decoded tokens
            # alone by it under-reports the engine (the old mislabeled
            # "tokens_per_s")
            "decode_tokens_per_s": (self._decoded / wall_s
                                    if wall_s else float("nan")),
            "total_tokens_per_s": (
                (self._decoded + self._prefilled) / wall_s
                if wall_s else float("nan")),
            "p50_latency_s": nearest_rank(lat, 50),
            "p99_latency_s": nearest_rank(lat, 99),
            "max_concurrent_decode": self._max_concurrent,
            "preemptions": sum(r.preemptions for r in self.requests.values()),
            "cancelled": self._cancelled,
            "scoring": {
                "requests": self._score_requests,
                "scored_tokens": self._score_tokens,
                "score_passes": self._score_passes,
            },
            "tenants": self.scheduler.tenant_report(),
            "speculative": self._spec_section(),
            "prefix_cache": prefix,
            "swap": c.swap_section(),
            "mixer": c.mixer_section(),
            "photonic": {
                **self.cost_model.report(),
                **self.cost_model.serving_report(
                    prefill_tokens=self._prefilled,
                    decode_tokens=self._decoded,
                    skipped_tokens=prefix["skipped_prefill_tokens"],
                    prefill_passes=self._prefill_calls,
                    prefill_chunk=self.ecfg.prefill_chunk),
                **self.cost_model.speculative_report(
                    verify_passes=self._spec_rows,
                    verify_tokens=self._verify_tokens,
                    committed_tokens=self._spec_committed),
                **self.cost_model.scoring_report(
                    score_tokens=self._score_tokens,
                    score_passes=self._score_passes),
            },
        }

    def _spec_section(self) -> dict:
        drafted = self._draft_tokens
        return {
            "enabled": self._spec_k > 0,
            "spec_k": self._spec_k,
            "spec_steps": self._spec_steps,
            "draft_tokens": drafted,
            "accepted_tokens": self._draft_accepted,
            "acceptance_rate": (self._draft_accepted / drafted
                                if drafted else 0.0),
            # committed tokens per scheduled decode ROW-step: 1.0 for
            # plain decoding, >1 when verify steps commit accepted
            # drafts on top of the verifier token
            "tokens_per_decode_step": (
                self._decode_produced / self._decode_rows
                if self._decode_rows else 0.0),
            "repairs": self._spec_repairs,
        }
