"""One module per model family: how a configuration file's keys map onto the
program's model (``model_config``), what a train loop needs of it (``Train``)
and what a serve cell needs of it (``Serve``). A family has one or both.
Found by the configuration's ``family`` key."""

import importlib
from typing import Dict

# What the correctness check of a serve cell sends unless the configuration
# file says otherwise under ``serve.check``: this many seeded prompts, of
# lengths drawn from ``prompt_len`` (both ends included), this many served
# tokens each.
SERVE_CHECK = {"prompts": 4, "prompt_len": [16, 128], "tokens": 8}


def load(name: str):
    return importlib.import_module(f"benchmarks.families.{name}")


def serve(config: Dict):
    """The ``Serve`` of a configuration's family, made from the
    configuration file; a family that only trains has none."""
    name = config["family"]
    family = load(name)
    if not hasattr(family, "Serve"):
        raise ValueError(
            f"family {name!r} has no Serve: benchmarks/families/{name}.py "
            f"describes training only, so a serve cell cannot run on it")
    return family.Serve(config)


def serve_check(config: Dict) -> Dict:
    """The check's sizes: ``SERVE_CHECK`` under the file's ``serve.check``."""
    return {**SERVE_CHECK, **config.get("serve", {}).get("check", {})}
