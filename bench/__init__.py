"""Chip benchmark for the serving runtime.

``bench/run.py`` runs one cell of ``BENCHMARK.json`` on the chip and prints
one JSON result line.  Everything that belongs to one configuration, one
model family, one traffic mix or one metric lives in a file of its own,
found by name: ``bench/configs/<config>.json``, ``bench/models/<model>.py``
(named by the configuration file; the interface is in its package's
docstring), ``bench/traffic/<traffic>.json`` (read by the generator module
it names) and ``bench/metrics/<metric>.py``.
"""
