"""One module per model family: how a configuration file's keys map onto the
program's model (``model_config``) and what a train loop needs of it
(``Train``). Found by the configuration's ``family`` key."""

import importlib


def load(name: str):
    return importlib.import_module(f"benchmarks.families.{name}")
