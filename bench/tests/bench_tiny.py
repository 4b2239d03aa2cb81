"""A tiny copy of a benchmark cell for the CPU tests: the cell's own
files, with every width and length cut so that a run takes seconds."""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import run  # noqa: E402

QWEN_SMALL = dict(n_layers=4, d_model=128, vocab=512, n_heads=4,
                  n_kv_heads=4, head_dim=32, d_ff=256)
SMALL = {"qwen-bnn.chat-steady": QWEN_SMALL, "qwen-bnn.rag-long": QWEN_SMALL}


def cell(name="qwen-bnn.chat-steady"):
    c = run.load_cell(name)
    small = SMALL[name]
    c.config = dict(c.config, **small)
    c.config["changed"] = dict(c.config["changed"], **small)
    c.mix = dict(c.mix,
                 prompt=dict(c.mix["prompt"], median=24, min=8, max=60),
                 output=dict(c.mix["output"], median=16, min=4, max=30))
    if "shared" in c.mix:
        c.mix["shared"] = dict(c.mix["shared"], doc_len=32,
                               question={"dist": "uniform", "min": 4,
                                         "max": 16})
    c.spec = dict(c.spec, rate_per_s=12.0, lead_in_s=1.0, trace_s=1.0)
    c.spec["engine"] = dict(c.spec["engine"], max_batch=4, prefill_chunk=16,
                            max_batched_tokens=20, max_model_len=96,
                            block_size=4, num_blocks=129,
                            max_tokens_in_flight=512)
    c.spec["check"] = dict(c.spec["check"], sample_tokens=64)
    return c


def serve(c, seed=2 ** 33 + 11, seconds=4.0, trace=False, fault=None,
          control=None):
    import time
    return run.run(c, seed, seconds, trace, require_tpu=False,
                   t_process=time.perf_counter(), fault=fault,
                   control=control)
