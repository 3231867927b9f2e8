"""The benchmark: a data-driven harness around one ``lgb.train`` call.

``BENCHMARK.json`` at the root of the repo is the manifest. Everything that
belongs to one configuration, one traffic mix, one cell's limits or one metric
is a file of its own under this directory, found by the name the manifest
gives; ``run.py`` names none of them.
"""
