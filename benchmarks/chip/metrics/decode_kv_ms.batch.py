"""``decode_kv_ms``, read the same way, in the cells judged on output tokens
per second: there it moves ``output_tok_per_s``, so it is a metric of
its own."""

from harness import metric_reader

read = metric_reader("decode_kv_ms").read
