"""The benchmark's own yardstick: traffic plumbing, references,
statistics, the trace reduction and the driver of one run. Nothing here
is imported by the program; of the benchmark, only ``stores/``, the ops'
``embedded`` calls, ``kernels/``, ``compile_cache.py``, ``instrument.py``
and ``controls.py`` import the program."""
