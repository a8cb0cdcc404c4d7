"""One driver per configuration kind: ``bench/drivers/<kind>.py``.

A configuration file names its ``kind``; `spec.load_driver` imports the
module of that name.  A driver exposes ``run_cell(config, traffic, seed,
seconds, traced, t_start_ns, peaks, chips, dtype=None)`` and returns
``(ctx, checks, attempted, failed, memory_peak_bytes)``: ``ctx`` is what
the cell's metric readers read, ``checks`` maps each number compared to
``(value, limit)``.  A new kind is a new file here.
"""
