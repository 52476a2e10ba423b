"""The yardstick: what ``benchmark/run.py`` is made of."""
