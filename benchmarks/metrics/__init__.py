"""One reader per per-layer metric, found by the name in BENCHMARK.json.

``<name>.py`` holds ``read(ctx)``: the number, or None where the run left
it nothing to read (the harness then leaves the metric out of the line).
An entry ``<name>.<suffix>`` with no file of its own is read by ``<name>.py``.
``ctx`` is the traced window as ``run.py`` gathered it: ``records`` (the
correct responses with their client latency, flight group, wall times and
parsed body, span tree included), ``before`` / ``after`` (the debug
endpoints around the window), ``device`` (``lib/trace_reduce.reduce`` or
None), ``in_trace`` (the records answered inside the traced span),
``config``, ``traffic``, ``cycle``, ``rows``, ``peak``, ``table_mod``.
"""
