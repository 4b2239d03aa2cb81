"""The engine's spans and the step programs' scopes, reduced to per-step
numbers: the idle split across nested phases, device time by scope with
the whole-pool copies, the fill share, the admission wait, and nothing
read where the program has no ``engine.*`` spans."""
import types

import pytest

import bench_tiny  # noqa: F401  (puts bench/ on the path)
import engine_spans as S
import run
import trace_reduce as T

NEW = ("host.schedule_idle_ms", "host.prepare_idle_ms",
       "host.dispatch_idle_ms", "host.commit_idle_ms", "step.kv_update_ms",
       "step.sample_ms", "step.weight_pack_ms", "step.fill_share",
       "sched.admit_wait_p95_ms")
POOL = "f32[2561,16,16,64]"


def op(name, start, dur, label=None, device=0):
    return T.Event(name, label or name, start, dur, device)


def span(name, start, dur, **stats):
    return S.Span(name, start, dur, stats)


def two_steps():
    """Step 7 decodes 4 rows of a bucket of 4, then step 8 prefills 200
    tokens of a 256 chunk.  The device idles from 1.0 to 2.0: through
    step 7's commit (0.9-1.3), the host loop between the steps
    (1.3-1.4), step 8's schedule (1.4-1.6) and its prepare (1.6-2.1),
    which holds a swap-in (1.7-1.8)."""
    spans = [span("step", 0.0, 1.3, step=7),
             span("schedule", 0.0, 0.1, step=7),
             span("decode.prepare", 0.1, 0.1, step=7, rows=4, bucket=4),
             span("decode.dispatch", 0.2, 0.1, step=7, rows=4, bucket=4),
             span("decode.sync", 0.3, 0.6, step=7, rows=4, bucket=4),
             span("decode.commit", 0.9, 0.4, step=7, rows=4, bucket=4),
             span("step", 1.4, 1.0, step=8),
             span("schedule", 1.4, 0.2, step=8),
             span("prefill.prepare", 1.6, 0.5, step=8, rid=3, tokens=200,
                  chunk=256),
             span("swap_in", 1.7, 0.1, step=8, rid=1, blocks=2),
             span("prefill.dispatch", 2.1, 0.1, step=8, rid=3, tokens=200,
                  chunk=256),
             span("prefill.commit", 2.2, 0.2, step=8, rid=3, tokens=200,
                  chunk=256)]
    ops = [op("%fusion.1", 0.25, 0.75),
           op("%copy.5", 2.0, 0.2, f"%copy.5 {POOL} {POOL}"),
           op("%copy.6", 2.2, 0.05, f"%copy.6 {POOL} {POOL}"),
           op("%sort.2", 2.25, 0.1),
           op("%paged_attention.3", 2.35, 0.02,
              f"%paged_attention.3 {POOL} tpu_custom_call"),
           op("%bnn_binarize_pack.4", 2.37, 0.03,
              "%bnn_binarize_pack.4 tpu_custom_call")]
    modules = [op("jit__prefill(42)", 2.0, 0.4)]
    programs = {"jit__prefill(42)": {
        "copy.6": "jit(_prefill)/attn/kv_update/scatter",
        "sort.2": "jit(_prefill)/sample/sort",
        "paged_attention.3": "jit(_prefill)/attn/paged_attention/"
                             "pallas_call",
        "bnn_binarize_pack.4": "jit(_prefill)/ffn/weight_pack/"
                               "bnn_binarize_pack/pallas_call"}}
    return spans, T.Trace(ops, modules, [], 1), programs


def test_idle_split_across_nested_phases():
    spans, tr, _ = two_steps()
    idle = S.idle_by_phase(tr.ops, spans)
    assert idle == pytest.approx({"commit": 0.3, "none": 0.1,
                                  "schedule": 0.2, "prepare": 0.4})
    # the swap-in nested in the prepare phase counts toward the phase
    assert "swap_in" not in idle
    assert sum(idle.values()) == pytest.approx(
        sum(b - a for (_, a), (b, _) in zip(T.union(tr.ops),
                                            T.union(tr.ops)[1:])))
    assert S.n_steps(spans) == 2


def test_fill_share_from_dispatch_stats():
    spans, _, _ = two_steps()
    # (200 tokens + 4 rows) / (256 chunk + 4 bucket)
    assert S.fill_share(spans) == pytest.approx(100 * 204 / 260)
    assert S.fill_share([s for s in spans if "dispatch" not in s.name]) \
        is None


def test_scope_time_counts_unscoped_whole_pool_copies_as_kv_update():
    _, tr, programs = two_steps()
    paths = S.op_paths(tr.ops, tr.modules, programs)
    assert paths[0] == ""               # outside every program run
    assert paths[3] == "jit(_prefill)/sample/sort"
    # copy.5 carries no scope: counted with the pool update; copy.6 is
    # the update's own scatter
    assert S.scope_time(tr.ops, paths, "kv_update", [POOL]) == \
        pytest.approx(0.25)
    assert S.scope_time(tr.ops, paths, "kv_update") == pytest.approx(0.05)
    assert S.scope_time(tr.ops, paths, "sample") == pytest.approx(0.1)
    assert S.scope_time(tr.ops, paths, "weight_pack") == \
        pytest.approx(0.03)
    assert S.scope_time(tr.ops, paths, "attn") == pytest.approx(0.07)
    # a TPU op event is named by its instruction's HLO text
    text = op("%sort.2 = (f32[8,151936]{1,0}, s32[8,151936]{1,0}) sort(...)",
              2.3, 0.01)
    assert S.instruction(text) == "sort.2"
    assert S.op_paths([text], tr.modules, programs) == \
        ["jit(_prefill)/sample/sort"]
    assert S.op_paths([text], [], programs) == [""]


def ctx_for(tr, requests=None, window=None):
    return types.SimpleNamespace(
        cell=run.load_cell("qwen-bnn.rag-long"), trace=tr,
        requests=requests or {}, window=window, pools=[POOL])


@pytest.fixture
def hand_built(monkeypatch):
    spans, tr, programs = two_steps()
    monkeypatch.setattr(T, "newest_xplane", lambda d: "hand-built")
    monkeypatch.setattr(S, "load_spans", lambda p: spans)
    monkeypatch.setattr(S, "program_op_names", lambda p: programs)
    S._CACHE.clear()
    yield ctx_for(tr)
    S._CACHE.clear()


def test_readers_on_a_hand_built_trace(hand_built):
    got = {n: run.load_reader(n).read(hand_built) for n in NEW[:8]}
    assert got["host.schedule_idle_ms"] == pytest.approx(1e3 * 0.2 / 2)
    assert got["host.prepare_idle_ms"] == pytest.approx(1e3 * 0.4 / 2)
    assert got["host.dispatch_idle_ms"] == 0.0
    assert got["host.commit_idle_ms"] == pytest.approx(1e3 * 0.3 / 2)
    assert got["step.kv_update_ms"] == pytest.approx(1e3 * 0.25 / 2)
    assert got["step.sample_ms"] == pytest.approx(1e3 * 0.1 / 2)
    assert got["step.weight_pack_ms"] == pytest.approx(1e3 * 0.03 / 2)
    assert got["step.fill_share"] == pytest.approx(100 * 204 / 260)


def test_admit_wait_censors_requests_not_admitted():
    w = types.SimpleNamespace(w0=10.0, w1=20.0, records=[])
    reqs = {}
    # admitted after 0.1 s each, one request due before the window
    for rid, due in enumerate([9.0, 11.0, 12.0, 13.0]):
        w.records.append(types.SimpleNamespace(rid=rid, due=due))
        reqs[rid] = types.SimpleNamespace(submit_s=due, admit_s=due + 0.1)
    # still queued at the window's end: 6 s so far
    w.records.append(types.SimpleNamespace(rid=4, due=14.0))
    reqs[4] = types.SimpleNamespace(submit_s=14.0, admit_s=None)
    # admitted only after the window: censored at its end, 1 s so far
    w.records.append(types.SimpleNamespace(rid=5, due=19.0))
    reqs[5] = types.SimpleNamespace(submit_s=19.0, admit_s=21.0)
    reader = run.load_reader("sched.admit_wait_p95_ms")
    ctx = ctx_for(None, reqs, w)
    # 5 requests due: 3 admitted at 100 ms, 2 waiting ranked above them
    assert reader.read(ctx) == pytest.approx(6000.0)
    del reqs[4], reqs[5]
    w.records = w.records[:4]
    assert reader.read(ctx) == pytest.approx(100.0)
    # a program that does not stamp admit_s reads nothing
    for r in reqs.values():
        del r.admit_s
    assert reader.read(ctx) is None


def test_nothing_read_without_engine_spans(tmp_path, monkeypatch):
    """A trace with no ``engine.*`` events (a program that predates them)
    reads None in every new reader, and none raises."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.sort(x * 2.0))
    f(jnp.ones(64)).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.step"):
        f(jnp.ones(64)).block_until_ready()
    jax.profiler.stop_trace()
    path = T.newest_xplane(str(tmp_path))
    assert S.load_spans(path) == []
    monkeypatch.setattr(S, "trace_dir", lambda ctx: str(tmp_path))
    S._CACHE.clear()
    _, tr, _ = two_steps()
    w = types.SimpleNamespace(w0=0.0, w1=1.0, records=[])
    ctx = ctx_for(tr, {}, w)
    for name in NEW:
        assert run.load_reader(name).read(ctx) is None, name
    S._CACHE.clear()


def test_program_op_names_from_a_profiled_program(tmp_path):
    """The wire decoding of the profiler's metadata plane finds each
    instruction's scope path."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def g(x):
        with jax.named_scope("sample"):
            return jnp.sort(jnp.sin(x))
    g(jnp.ones(32)).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    g(jnp.ones(32)).block_until_ready()
    jax.profiler.stop_trace()
    progs = S.program_op_names(T.newest_xplane(str(tmp_path)))
    mine = [v for k, v in progs.items() if k.startswith("jit_g(")]
    assert mine
    assert any(S.in_scope(p, "sample") and p.endswith("/sort")
               for p in mine[0].values())


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _msg(field: int, payload: bytes) -> bytes:
    return _varint(field << 3 | 2) + _varint(len(payload)) + payload


def _instruction(name, iid, operands=(), op_name=None) -> bytes:
    b = _msg(1, name.encode()) + _varint(35 << 3) + _varint(iid)
    if operands:
        b += _msg(36, b"".join(_varint(i) for i in operands))
    if op_name is not None:
        b += _msg(7, _msg(2, op_name.encode()))
    return b


def test_unscoped_instructions_take_their_operands_scope():
    """XLA's own instructions (an expanded sort's pieces, a layout copy)
    carry no ``op_name``: they take the first operand's path."""
    comp = b"".join(_msg(2, i) for i in [
        _instruction("param.0", 1, op_name="pools[0]['k']"),
        _instruction("sort.1", 2, [1], "jit(_decode)/sample/sort"),
        _instruction("get-tuple-element.3", 3, [2]),
        _instruction("fusion.147", 4, [3, 1]),
        _instruction("copy.5", 5, [1], "pool"),
        _instruction("constant.6", 6)])
    proto = _msg(1, _msg(3, comp))
    got = S.hlo_op_names(proto)
    assert got["fusion.147"] == got["get-tuple-element.3"] \
        == "jit(_decode)/sample/sort"
    assert got["copy.5"] == "pool"      # its own op_name stands
    assert "constant.6" not in got      # no op_name, no operands
