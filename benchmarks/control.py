#!/usr/bin/env python3
"""The control of a serve cell's ``correct``: the family's plain reference
put in the program's place, computed one precision below the replica's
(int8 weights for bfloat16 compute), has to come out as NOT correct.

    python3 benchmarks/control.py --config benchmarks/configs/<c>.json \\
        --seeds 1 2 3 [--bits 8]

One process that holds the chip; no runtime, no HTTP, no timed window. For
each seed it makes the cell's weights as the replica does, draws the
check's prompts as ``serve_cell`` does, lets the rounded reference answer,
and prints the largest served-token margin of those answers under the
float32 reference beside the family's tolerance. It is not part of a
benchmark run; ``PERF.md`` keeps its readings."""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--bits", type=int, default=8)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    from benchmarks import families, run, serve_cell

    with open(args.config) as f:
        config = json.load(f)
    if args.rehearse:
        config = run.merge(config, config.get("rehearse", {}))
    fam = families.serve(config)
    dev = jax.devices()[0]
    print(f"[control] {dev.platform} {dev.device_kind}, reference "
          f"{fam.reference}, weights rounded to {args.bits} bits", flush=True)
    readings = []
    for seed in args.seeds:
        reqs = serve_cell.check_requests(fam.check, seed)
        prompts = [r.tokens(fam.vocab) for r in reqs]
        worst = max(fam.control_margins(seed % (2 ** 31 - 1), prompts,
                                        fam.check["tokens"], args.bits))
        readings.append(worst)
        print(f"[control] seed {seed}: largest margin {worst:.4f} "
              f"(tolerance {fam.tolerance}): "
              f"{'NOT correct' if worst > fam.tolerance else 'passes'}",
              flush=True)
    print(json.dumps({"control_margin_min": min(readings),
                      "control_margin_max": max(readings),
                      "tolerance": fam.tolerance, "bits": args.bits,
                      "platform": dev.platform, "kind": dev.device_kind}))


if __name__ == "__main__":
    main()
