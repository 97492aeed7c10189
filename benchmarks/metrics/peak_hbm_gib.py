"""Peak device memory on the fullest chip after the window, in GiB: the
figure `device.memory_peak_bytes` carries (benchmarks/lib/harness.py:
memory_peak_bytes says which allocator counters it is made of and why)."""

DECLARATION = {"name": "peak_hbm_gib", "unit": "GiB", "better": "lower", "source": "program_counter",
               "layer": "memory", "moves": "step_ms"}


def read(ctx):
    peak = ctx["memory_peak_bytes"]
    return peak / 2**30 if peak else None
