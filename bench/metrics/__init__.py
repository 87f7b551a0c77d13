"""One reader per per-layer metric: ``read(ctx)`` returns the metric's
value, or None where the run gave it nothing to read.

``ctx`` carries the configuration (``config``), the cohort ``C``, the
rounds to a block ``k``, the trace reduction (``trace``, see
``bench/tracing.py``; None without a device trace), the host seconds of
every eval (``eval_s``) and the chip's peaks (``peaks``).
"""
