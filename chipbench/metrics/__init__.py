"""One module per metric of BENCHMARK.json, named after it (a dot in the
name becomes an underscore).  Each defines ``read(ctx)``, which returns the
metric's value, or None where the run holds nothing to read it from.

``ctx`` holds ``window`` (seconds, net_steps, calls of the measured
window), ``setup`` (setup_s, compile_s, build_s), ``trace`` (the reduced
profiler trace of a traced run, else None), ``peak`` (the device's peaks)
and ``work`` (the window's necessary bytes and operations)."""
