"""Record ``data/tiny_spans.xplane.pb.gz`` on a TPU: a DeepSeek-MoE stage
at small widths (d_model 256, 8 experts, top-2, one shared, 2 layers)
serving six requests through the Router, with the program's spans and
named scopes and the harness's ``bench.step`` span around each
``Router.step``. Prints the idle attribution of ``bench/spans.py``.

    python3 tests/bench/make_span_trace.py <out_dir>
"""
import copy
import gzip
import shutil
import sys
from pathlib import Path

import jax
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
from bench import common, program, spans, weights  # noqa: E402


def main(out: str) -> None:
    c = copy.deepcopy(common.config("deepseek-moe-16b-s7"))
    c.update(hidden_size=256, num_attention_heads=4, num_key_value_heads=4,
             head_dim=64, moe_intermediate_size=128, n_routed_experts=8,
             num_experts_per_tok=2, n_shared_experts=1, vocab_size=1024,
             num_hidden_layers=2)
    c["deployment"].update(max_slots=4, max_seq_len=128)
    r = program.router(c, weights.model_params(c, 1))
    rng = np.random.default_rng(0)

    def serve(lengths):
        for n in lengths:
            r.submit(rng.integers(0, 1024, n, dtype=np.int32), 8)
        while r.has_work():
            with jax.profiler.TraceAnnotation("bench.step"):
                r.step()

    serve((20, 40, 100, 20, 40, 100))          # compiles every shape
    d = Path(out) / "trace"
    shutil.rmtree(d, ignore_errors=True)
    jax.profiler.start_trace(str(d))
    serve((20, 40, 100, 30, 60, 90))
    jax.profiler.stop_trace()
    r.close()
    path = spans.newest(d)
    with open(path, "rb") as src, \
            gzip.open(Path(out) / "tiny_spans.xplane.pb.gz", "wb") as dst:
        shutil.copyfileobj(src, dst)
    print(spans.report(spans.reduce(path)))


if __name__ == "__main__":
    main(sys.argv[1])
