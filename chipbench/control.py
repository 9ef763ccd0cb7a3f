"""The precision control: the plain reference put in the program's place
at the next precision below the configuration's (int4 for int8), read by
the same comparison that decides ``correct``.

    python3 -m chipbench.control --workload resnet50-224.closed \
        --seeds 1,2,3

For each seed it builds the cell's weights and input pool as a run does,
computes the reference at float32 and at the precision below the
configuration's (activations per tensor over each batch of the traffic's
largest bucket, weights per output channel), and prints the numbers
compared as one JSON line.  The control has to read above the cell's
limits; ``PERF.md`` records what it read on the chip.  It runs where it
is started (no look for a chip).
"""
from __future__ import annotations

import argparse
import json
import sys

from chipbench import check, spec

LOWER = {"float32": 16, "bfloat16": 8, "int8": 4}  # bits of the control


def control_numbers(cell, seed: int) -> dict:
    import jax

    from chipbench.systems import cnn
    from chipbench.env import Env
    from chipbench.loadgen import Request

    cfg, traffic = cell.config, cell.traffic
    bits = LOWER[cfg["precision"]]
    ref = spec.load_module(cell.root, "refs", cfg["reference"])
    key = Env.seed_key(seed)
    params = cnn._make_weights(ref, cfg, jax.random.fold_in(key, 0))
    images = cnn._images(cfg, traffic, jax.random.fold_in(key, 1))
    want = cnn.reference_logits(ref, cfg, params, images)
    batch = traffic["serving"]["buckets"][-1]
    got = cnn.reference_logits(ref, cfg, params, images, bits=bits,
                               block=batch)
    reqs = [Request(pool=i, due=0.0, done=0.0, logits=got[i])
            for i in range(len(images))]
    return check.numbers(reqs, want)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(spec.REPO / "src"))
    cell = spec.load_cell(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        nums = control_numbers(cell, seed)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": nums, "limits": cell.limits}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
