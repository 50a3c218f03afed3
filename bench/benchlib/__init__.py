"""The benchmark's own code: cell lookup, traffic, weights, the serving
window, trace reduction, the chips' peaks and the correctness check.
What depends on a model's architecture is in ``bench/archs/``, what
depends on a kernel in ``bench/kernels/``. Only ``serve.py`` and the
architecture files' ``program_config`` import the program under test."""
