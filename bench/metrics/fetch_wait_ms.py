"""fetch_wait_ms (ms, program span): mean, over the executor's
``serve.flush`` spans in the traced window, of the summed durations of
the ``serve.flush.fetch`` spans inside each: the executor in the query
drivers, from their dispatch to their result on the host.  Less
``engine_device_ms``, how long a query's programs waited behind other
device work (the writer's merges, ``engine_aux``) and their dispatch."""
from bench.harness import BENCH, load_module

_spans = load_module(BENCH / "metrics" / "_spans.py")


def read(rec):
    return _spans.inner_sum_ms(rec, "serve.flush", "serve.flush.fetch")
