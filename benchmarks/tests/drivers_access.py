"""The two drivers, loaded the way ``run.py`` loads them."""

import toy  # noqa: F401  (puts benchmarks/ on the path)
from harness.loading import load_module

train = load_module("drivers", "train_epochs")
serve = load_module("drivers", "serve_open_loop")
