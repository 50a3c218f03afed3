"""The benchmark's own code: cell lookup, traffic, weights, the serving
window, trace reduction, roofline arithmetic and the correctness check.
Only ``serve.py`` imports the program under test."""
