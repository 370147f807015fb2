"""The chip benchmark: ServingEngine on one TPU chip, cell by cell, as
``BENCHMARK.json`` at the root of the checkout describes it."""
