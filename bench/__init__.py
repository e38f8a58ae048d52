"""The chip benchmark of the Flint engine: data-driven cells of
deployments and traffic, run by ``bench/run.py`` (see BENCHMARK.json)."""
