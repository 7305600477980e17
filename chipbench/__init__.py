"""The chip benchmark of the Morph engine (``BENCHMARK.json`` at the root;
``python3 chipbench/run.py`` is its command)."""
